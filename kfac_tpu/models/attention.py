"""Attention kernels: dense causal and ring (context-parallel) attention.

Ring attention shards the sequence axis over a mesh axis and rotates K/V
blocks around the ring with ``ppermute`` while accumulating output in the
numerically-stable blockwise-softmax (flash) form. This gives
sequence-length scaling the reference framework does not have (SURVEY.md
section 2.3 lists SP/CP as absent) with communication that rides the ICI
ring — each step overlaps a block matmul with the next block's transfer.

Causal runs skip fully-masked (above-diagonal) blocks entirely. The ring is
still lockstep, so the tail shard's diagonal-heavy load bounds wall clock;
zigzag position striping plus a block-sparse Pallas kernel is the planned
next level.

Matmuls accumulate in fp32 (``preferred_element_type``); inputs may be bf16.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def dense_causal_attention(q, k, v):
    """Single-device causal attention: (B, S, H, D) -> (B, S, H, D).

    On TPU with tile-aligned shapes this dispatches to the Pallas flash
    kernel (ops/pallas_attention): scores stay in VMEM and above-diagonal
    K tiles are skipped. Elsewhere the dense einsum path runs.
    """
    from kfac_tpu import tracing
    from kfac_tpu.ops import pallas_attention as pa

    with tracing.model_scope('attention'):
        if pa.use_flash_for(
            q.shape[1], k.shape[1], q.shape[-1], q.dtype.itemsize, dense=True
        ):
            out = _finish(pa.flash_attention_partials(q, k, v, causal=True))
            return out.astype(q.dtype)
        out = _finish(pa.attend_partials_einsum(q, k, v, 0, 0, True))
        return out.astype(q.dtype)


def blockwise_causal_attention(q, k, v, chunk: int = 1024):
    """Causal attention by online softmax over chunks of keys, grouped
    queries included: ``q`` ``(B, S, H, D)``, ``k``/``v`` ``(B, S, H_kv,
    D)`` with every key/value head serving ``H / H_kv`` query heads.

    The scores of one (query chunk, key chunk) pair exist at a time and
    are not kept for the backward pass (each pair's partials are
    rematerialised), so memory is ``O(S * chunk)`` where the dense path's
    is ``O(S^2)``; chunk pairs above the diagonal are never formed. The
    pairs go through :func:`_block_attend` and :func:`_merge`, as the
    ring's do.
    """
    s, h, h_kv = q.shape[1], q.shape[2], k.shape[2]
    if h % h_kv:
        raise ValueError(f'{h} query heads over {h_kv} key/value heads')
    if h != h_kv:
        k, v = (jnp.repeat(x, h // h_kv, axis=2) for x in (k, v))
    if s <= chunk or s % chunk:
        chunk = s
    attend = jax.checkpoint(_block_attend, static_argnums=(3, 4, 5))
    out = []
    for i in range(s // chunk):
        qi = q[:, i * chunk:(i + 1) * chunk]
        carry = None
        for j in range(i + 1):
            kv = slice(j * chunk, (j + 1) * chunk)
            blk = attend(qi, k[:, kv], v[:, kv], i * chunk, j * chunk, i == j)
            carry = blk if carry is None else _merge(carry, blk)
        out.append(_finish(carry))
    return jnp.concatenate(out, axis=1).astype(q.dtype)


def _block_attend(q, k, v, q_offset, k_offset, causal):
    """Unnormalized blockwise attention: returns (acc, row_max, row_sum).

    On TPU with tile-aligned chunks the Pallas flash kernel computes the
    partials (global offsets flow in as scalar prefetch, so causal tile
    skipping tracks the ring position); elsewhere the einsum
    implementation runs (ops/pallas_attention.attend_partials_einsum —
    also the kernel's backward and interpret-mode oracle).
    """
    from kfac_tpu.ops import pallas_attention as pa

    if pa.use_flash_for(
        q.shape[1], k.shape[1], q.shape[-1], q.dtype.itemsize
    ):
        return pa.flash_attention_partials(
            q, k, v, q_offset=q_offset, k_offset=k_offset, causal=causal
        )
    return pa.attend_partials_einsum(q, k, v, q_offset, k_offset, causal)


def _merge(carry, blk):
    """Log-sum-exp merge of two blockwise-softmax partials (flash form)."""
    acc, m, l = carry
    blk_acc, blk_m, blk_l = blk
    new_m = jnp.maximum(m, blk_m)
    scale_old = jnp.exp(m - new_m)
    scale_blk = jnp.exp(blk_m - new_m)
    l = l * scale_old + blk_l * scale_blk
    acc = (
        acc * scale_old.transpose(0, 2, 1)[..., None]
        + blk_acc * scale_blk.transpose(0, 2, 1)[..., None]
    )
    return acc, new_m, l


def _finish(carry):
    """Normalize accumulated blockwise output (guarding fully-masked rows)."""
    acc, _, l = carry
    denom = jnp.where(l == 0.0, 1.0, l)
    return acc / denom.transpose(0, 2, 1)[..., None]


def ring_attention(q, k, v, axis_name: str, causal: bool = True):
    """Context-parallel attention inside ``shard_map``.

    Args:
        q, k, v: local sequence shards (B, S_local, H, D); the global
            sequence is sharded over ``axis_name`` in ring order.
        axis_name: mesh axis carrying the sequence shards.
        causal: apply a causal mask in *global* positions.

    Returns (B, S_local, H, D): this shard's rows of the attention output,
    exactly equal to the dense computation on the gathered sequence.
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    q_offset = my * s_local

    perm = [(j, (j + 1) % n) for j in range(n)]

    # Iteration 0 (own block) runs outside the loop so K/V rotate only
    # n-1 times; later iterations rotate at the top of the body.
    carry0 = _block_attend(q, k, v, q_offset, q_offset, causal)

    def body(i, state):
        acc, m, l, k_cur, v_cur = state
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (my - i) % n
        k_offset = src * s_local

        def attend(_):
            blk = _block_attend(q, k_cur, v_cur, q_offset, k_offset, causal)
            return _merge((acc, m, l), blk)

        if causal:
            # blocks strictly above the diagonal are fully masked: skip the
            # matmuls entirely (predicate is device-local; no collectives in
            # either branch)
            acc, m, l = jax.lax.cond(
                src > my, lambda _: (acc, m, l), attend, operand=None
            )
        else:
            acc, m, l = attend(None)
        return acc, m, l, k_cur, v_cur

    acc, m, l, _, _ = jax.lax.fori_loop(
        1, n, body, (*carry0, k, v)
    )
    return _finish((acc, m, l)).astype(q.dtype)


def zigzag_ring_attention(q, k, v, axis_name: str):
    """Load-balanced causal ring attention inside ``shard_map``.

    The naive causal ring is lockstep but skewed: shard j attends j+1
    blocks, so the last shard bounds wall clock. Zigzag striping gives each
    device TWO global chunks — chunk ``my`` and its mirror ``2n-1-my`` —
    making every device's causal workload identical (2n+1 chunk-attends
    total; exactly two per ring step, three on the diagonal step):

    - q-chunk ``my`` vs incoming chunk ``src``: attends iff src <= my
    - q-chunk ``2n-1-my`` vs ``src``: always attends (mirror is late)
    - q-chunk ``2n-1-my`` vs ``2n-1-src``: attends iff src >= my
    - q-chunk ``my`` vs ``2n-1-src``: NEVER (mirror K is always later) —
      statically skipped.

    Local layout: rows [0:c) are global chunk ``my``, rows [c:2c) the
    mirror, with c = S_local/2 (see :func:`zigzag_indices`). Beyond the
    reference (which has no context parallelism at all); the balanced
    schedule follows the public zigzag ring-attention recipe.
    """
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = q.shape[1]
    if s_local % 2:
        raise ValueError('zigzag shards hold two chunks; S_local must be even')
    c = s_local // 2
    qa, qb = q[:, :c], q[:, c:]
    off_a = my * c                 # global offset of chunk `my`
    off_b = (2 * n - 1 - my) * c   # global offset of the mirror chunk

    perm = [(j, (j + 1) % n) for j in range(n)]

    def maybe(pred, carry, qc, q_off, kc, vc, k_off):
        return jax.lax.cond(
            pred,
            lambda _: _merge(
                carry, _block_attend(qc, kc, vc, q_off, k_off, True)
            ),
            lambda _: carry,
            operand=None,
        )

    def step(src, carry_a, carry_b, k_cur, v_cur):
        k1, k2 = k_cur[:, :c], k_cur[:, c:]
        v1, v2 = v_cur[:, :c], v_cur[:, c:]
        k1_off = src * c
        k2_off = (2 * n - 1 - src) * c
        carry_a = maybe(src <= my, carry_a, qa, off_a, k1, v1, k1_off)
        # the mirror q-chunk is later than every incoming first K-chunk:
        # this attend is unconditional
        carry_b = _merge(
            carry_b, _block_attend(qb, k1, v1, off_b, k1_off, True)
        )
        carry_b = maybe(src >= my, carry_b, qb, off_b, k2, v2, k2_off)
        return carry_a, carry_b

    def zero_carry(qc):
        b, _, h, _ = qc.shape
        zeros = (
            jnp.zeros((b, c, h, qc.shape[-1]), jnp.float32),
            jnp.full((b, h, c), NEG_INF, jnp.float32),
            jnp.zeros((b, h, c), jnp.float32),
        )
        # the attended branches are device-varying; the initial carry must
        # match their vma for lax.cond
        return tuple(
            jax.lax.pcast(z, (axis_name,), to='varying') for z in zeros
        )

    carry_a, carry_b = step(my, zero_carry(qa), zero_carry(qb), k, v)

    def body(i, state):
        carry_a, carry_b, k_cur, v_cur = state
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        src = (my - i) % n
        carry_a, carry_b = step(src, carry_a, carry_b, k_cur, v_cur)
        return carry_a, carry_b, k_cur, v_cur

    carry_a, carry_b, _, _ = jax.lax.fori_loop(
        1, n, body, (carry_a, carry_b, k, v)
    )

    return jnp.concatenate(
        [_finish(carry_a), _finish(carry_b)], axis=1
    ).astype(q.dtype)


def zigzag_indices(seq_len: int, n_shards: int):
    """Permutation taking a natural-order sequence to zigzag shard layout.

    Shard j receives chunks (j, 2n-1-j) of size seq_len/(2n). Returns
    (perm, inv) index arrays: ``x_zigzag = x[:, perm]``,
    ``x_natural = y[:, inv]``. At production scale the zigzag layout is
    kept end to end (embedding/loss are position-independent row maps);
    the wrapper below permutes globally for API simplicity.
    """
    import numpy as np

    if seq_len % (2 * n_shards):
        raise ValueError(f'{seq_len=} not divisible by 2*{n_shards=}')
    c = seq_len // (2 * n_shards)
    perm = np.concatenate(
        [
            np.concatenate(
                [
                    np.arange(j * c, (j + 1) * c),
                    np.arange(
                        (2 * n_shards - 1 - j) * c,
                        (2 * n_shards - j) * c,
                    ),
                ]
            )
            for j in range(n_shards)
        ]
    )
    inv = np.argsort(perm)
    return perm, inv


def make_context_parallel_attention(
    mesh,
    axis_name: str,
    causal: bool = True,
    num_heads: int | None = None,
    zigzag: bool = False,
):
    """shard_map-wrapped ring attention over global (B, S, H, D) arrays.

    Besides the sequence axis, the batch dim stays sharded over any
    data-parallel axes present in the mesh and heads over a model axis when
    ``num_heads`` is given and divisible by it (otherwise heads replicate) —
    ring attention must not undo data/tensor parallelism.

    ``zigzag=True`` (causal only) uses the load-balanced zigzag striping:
    inputs are permuted into zigzag chunk order, attended, and permuted
    back, so callers keep natural sequence order.
    """
    from jax.sharding import PartitionSpec as P

    from kfac_tpu.parallel import mesh as mesh_lib

    batch_axes = tuple(a for a in mesh_lib.DATA_AXES if a in mesh.shape)
    head_axis = None
    if (
        mesh_lib.MODEL_AXIS in mesh.shape
        and mesh.shape[mesh_lib.MODEL_AXIS] > 1
        and num_heads is not None
        and num_heads % mesh.shape[mesh_lib.MODEL_AXIS] == 0
    ):
        head_axis = mesh_lib.MODEL_AXIS
    spec = P(batch_axes or None, axis_name, head_axis, None)

    if zigzag:
        if not causal:
            raise ValueError(
                'zigzag balances the causal workload; use zigzag=False for '
                'non-causal attention'
            )
        n_shards = int(mesh.shape[axis_name])
        sharded = jax.shard_map(
            functools.partial(zigzag_ring_attention, axis_name=axis_name),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
        )

        def apply(q, k, v):
            perm, inv = zigzag_indices(q.shape[1], n_shards)
            out = sharded(q[:, perm], k[:, perm], v[:, perm])
            return out[:, inv]

        return apply

    fn = functools.partial(ring_attention, axis_name=axis_name, causal=causal)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
