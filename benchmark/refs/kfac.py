"""K-FAC by the documented formulas (SURVEY.md, "What the system is"), plain.

    A = E[a^T a]   (layer inputs; a column of ones for a bias; im2col
                    patches over the output's spatial size for a conv)
    G = E[g^T g]   (gradients of the mean loss w.r.t. layer outputs)
    F_t = decay * F_{t-1} + (1 - decay) * F,   F_{-1} = I
    P = (G_t + damping I)^-1  dW  (A_t + damping I)^-1
    P *= min(1, sqrt(kl_clip / |sum_layers <P, dW> lr^2|))

Float32 with every product at ``Precision.HIGHEST``; the inverses by
LAPACK's float32 Cholesky pair on the host (a TPU lowers ``jnp.linalg.inv``
to sequential panel algorithms that take minutes at these widths; the
damped factors are symmetric positive definite, and ``potrf`` + ``potri``
takes a quarter of the time of a float64 ``np.linalg.inv`` and agrees
with it to 2e-7 of the inverse's norm: PERF.md section 2).
Nothing here imports ``kfac_tpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from scipy.linalg import lapack

HI = lax.Precision.HIGHEST


def cov(rows: jax.Array) -> jax.Array:
    """``rows^T rows / n`` for (n, d) rows."""
    return jnp.matmul(rows.T, rows, precision=HI) / rows.shape[0]


def dense_a(x: jax.Array, has_bias: bool) -> jax.Array:
    rows = x.reshape(-1, x.shape[-1])
    if has_bias:
        rows = jnp.concatenate(
            [rows, jnp.ones((rows.shape[0], 1), rows.dtype)], axis=1
        )
    return cov(rows)


def dense_g(g: jax.Array) -> jax.Array:
    return cov(g.reshape(-1, g.shape[-1]))


def conv_a(x, kernel_hw, strides, padding) -> jax.Array:
    """Patches are channel-major (c, kh, kw); rows are divided by the
    output's spatial size (the reference's KFC normalisation)."""
    patches = lax.conv_general_dilated_patches(
        x, filter_shape=kernel_hw, window_strides=strides, padding=padding,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'), precision=HI,
    )
    spatial = patches.shape[1] * patches.shape[2]
    return cov(patches.reshape(-1, patches.shape[-1]) / spatial)


def conv_g(g: jax.Array) -> jax.Array:
    spatial = g.shape[1] * g.shape[2]
    return cov(g.reshape(-1, g.shape[-1]) / spatial)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def g_tap(y, slot, g_fn):
    """Identity on ``y``. Its backward pass hands ``g_fn(dL/dy)`` to
    ``slot`` as its cotangent, so the gradient w.r.t. a zero ``slot`` of
    the factor's shape *is* the layer's G factor and no output gradient
    has to be kept."""
    del slot
    return y


def _g_tap_fwd(y, slot, g_fn):
    del slot
    return y, None


def _g_tap_bwd(g_fn, _, g):
    return g, g_fn(g)


g_tap.defvjp(_g_tap_fwd, _g_tap_bwd)


# ------------------------------------------------------- matrix <-> leaves


def dense_to_matrix(layer: dict) -> jax.Array:
    """(d_in, d_out) kernel [+ bias] -> (d_out, d_in [+ 1])."""
    mat = layer['kernel'].T
    if 'bias' in layer:
        mat = jnp.concatenate([mat, layer['bias'][:, None]], axis=1)
    return mat


def matrix_to_dense(mat: jax.Array, like: dict) -> dict:
    if 'bias' in like:
        return {'kernel': mat[:, :-1].T, 'bias': mat[:, -1]}
    return {'kernel': mat.T}


def conv_to_matrix(layer: dict) -> jax.Array:
    """(kh, kw, c_in, c_out) kernel -> (c_out, c_in * kh * kw)."""
    k = layer['kernel']
    return jnp.transpose(k, (3, 2, 0, 1)).reshape(k.shape[3], -1)


def matrix_to_conv(mat: jax.Array, like: dict) -> dict:
    kh, kw, cin, cout = like['kernel'].shape
    return {
        'kernel': jnp.transpose(mat.reshape(cout, cin, kh, kw), (2, 3, 1, 0))
    }


def to_matrix(layer: dict) -> jax.Array:
    if layer['kernel'].ndim == 4:
        return conv_to_matrix(layer)
    return dense_to_matrix(layer)


def from_matrix(mat: jax.Array, like: dict) -> dict:
    if like['kernel'].ndim == 4:
        return matrix_to_conv(mat, like)
    return matrix_to_dense(mat, like)


# ------------------------------------------------------------------ the step


def spd_inverse(m: np.ndarray) -> np.ndarray:
    """The inverse of a symmetric positive definite float32 matrix by
    LAPACK's Cholesky pair (``spotrf``, ``spotri``)."""
    c, info = lapack.spotrf(m, lower=1, clean=1)
    if info:
        raise np.linalg.LinAlgError(f'spotrf: info {info}: not positive definite')
    inv, info = lapack.spotri(c, lower=1, overwrite_c=1)
    if info:
        raise np.linalg.LinAlgError(f'spotri: info {info}')
    # potri fills the lower triangle; the upper is potrf's clean zeros
    i = np.arange(len(inv))
    diag = inv[i, i].copy()
    inv = inv + inv.T
    inv[i, i] = diag
    return inv


def first_inverses(factors: dict, decay, damping) -> dict:
    """Damped inverses of every layer's factor after its first EMA step
    from the identity: ``(decay I + (1 - decay) F + damping I)^-1``, on the
    host, back on the device in float32 where the factor was."""
    out = {}
    for name, f in factors.items():
        m = (1.0 - decay) * np.asarray(jax.device_get(f), np.float32)
        i = np.arange(len(m))
        m[i, i] += np.float32(decay + damping)
        out[name] = jax.device_put(spd_inverse(m), f.sharding)
    return out


def flatten(tree: dict, prefix: str = '') -> dict:
    """{'a/b/c': leaf} of a nested dict; ``None`` leaves are dropped."""
    flat = {}
    for k, v in tree.items():
        path = f'{prefix}/{k}' if prefix else k
        if isinstance(v, dict):
            flat.update(flatten(v, path))
        elif v is not None:
            flat[path] = v
    return flat


def get_path(tree: dict, path: str) -> dict:
    for key in path.split('/'):
        tree = tree[key]
    return tree


def set_path(tree: dict, path: str, value: dict) -> dict:
    keys = path.split('/')
    if len(keys) == 1:
        return {**tree, keys[0]: value}
    return {**tree, keys[0]: set_path(tree[keys[0]], '/'.join(keys[1:]), value)}


@functools.partial(jax.jit, static_argnames=('layers',))
def precondition(grads, a_inv, g_inv, lr, kl_clip, layers):
    """Precondition the named layers of a gradient tree and kl-clip them;
    every other leaf passes through."""
    pmats, vg = {}, jnp.zeros((), jnp.float32)
    for name in layers:
        gmat = to_matrix(get_path(grads, name))
        pmat = jnp.matmul(
            jnp.matmul(g_inv[name], gmat, precision=HI), a_inv[name],
            precision=HI,
        )
        vg = vg + jnp.sum(pmat * gmat) * lr ** 2
        pmats[name] = pmat
    scale = jnp.where(
        vg == 0.0, 1.0, jnp.minimum(1.0, jnp.sqrt(kl_clip / jnp.abs(vg)))
    )
    out = grads
    for name in layers:
        out = set_path(
            out, name, from_matrix(pmats[name] * scale, get_path(grads, name))
        )
    return out


@functools.partial(
    jax.jit, static_argnames=('momentum', 'weight_decay', 'clip_norm')
)
def sgd_step(params, trace, grads, lr, momentum, weight_decay, clip_norm):
    """optax's chain, written out: [clip by global norm |
    add decayed weights] -> momentum trace -> ``p -= lr * trace``.
    Returns ``(params, trace)``."""
    if clip_norm is not None:
        norm = jnp.sqrt(sum(
            jnp.sum(g * g) for g in jax.tree_util.tree_leaves(grads)
        ))
        grads = jax.tree_util.tree_map(
            lambda g: jnp.where(norm < clip_norm, g, g * (clip_norm / norm)),
            grads,
        )
    if weight_decay:
        grads = jax.tree_util.tree_map(
            lambda g, p: g + weight_decay * p, grads, params
        )
    trace = jax.tree_util.tree_map(
        lambda g, t: g + momentum * t, grads, trace
    )
    params = jax.tree_util.tree_map(lambda p, t: p - lr * t, params, trace)
    return params, trace


@jax.jit
def leaf_norms(tree) -> dict:
    """{path: l2 norm} over the leaves, paths joined by '/'."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        '/'.join(str(getattr(k, 'key', k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for path, leaf in flat
    }


def unload() -> None:
    """Drop the compiled update programs of this module, which a run's
    reference leaves loaded (they are jitted at module level): the
    harness samples device memory only with no program of the reference
    on the device. ``leaf_norms`` stays: the check takes the program's
    norms with it."""
    precondition.clear_cache()
    sgd_step.clear_cache()
