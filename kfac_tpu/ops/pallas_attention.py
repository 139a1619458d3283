"""Pallas TPU flash-attention kernel emitting blockwise-softmax partials.

The attention hot path appears twice in this framework: the dense causal
path (models/attention.dense_causal_attention, which materializes the full
S x S score matrix in HBM) and the per-step chunk attends inside ring /
zigzag context parallelism (models/attention._block_attend). Both reduce to
the same primitive: *unnormalized* blockwise-softmax partials
``(acc, m, l)`` over one (Q-chunk, K-chunk) pair that the caller merges in
log-sum-exp form (the flash recipe). This kernel computes that primitive
tiled in VMEM — scores never touch HBM — with the causal structure applied
at *global* positions carried in scalar-prefetch offsets, so the same
kernel serves the dense case (offsets 0) and any ring step (chunk offsets).

Block-sparsity: inside the kernel each Q tile loops only over K tiles that
intersect its causal triangle (a dynamic upper bound computed from the
prefetched offsets) — fully-masked K tiles are never loaded or multiplied.
Under the zigzag schedule this is the intra-chunk complement to the
schedule's whole-chunk skipping: together, compute tracks the true causal
area at both granularities.

The reference has no attention kernels at all (it preconditions
torch modules); this sits beyond parity, next to ring attention.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from kfac_tpu.ops import pallas_gate

NEG_INF = -1e30

BLOCK_Q = 128
BLOCK_K = 128
# lane width of the m/l output tiles (TPU vector lane count); see
# _flash_kernel's broadcast stores
_LANE = 128


def _flash_kernel(
    offs_ref,      # scalar prefetch: [q_offset, k_offset] (SMEM)
    q_ref,         # (1, BLOCK_Q, D) VMEM
    k_ref,         # (1, S_k, D) VMEM
    v_ref,         # (1, S_k, D) VMEM
    acc_ref,       # (1, BLOCK_Q, D) out
    m_ref,         # (1, BLOCK_Q, _LANE) out (value broadcast across lanes)
    l_ref,         # (1, BLOCK_Q, _LANE) out (value broadcast across lanes)
    *,
    causal: bool,
    block_k: int,
    n_k: int,
):
    j = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    scale = q.shape[-1] ** -0.5
    q = q * scale
    q_off = offs_ref[0]
    k_off = offs_ref[1]
    block_q = q.shape[0]

    if causal:
        # last K tile this Q tile can see: global causal bound, dynamic in
        # the ring offsets. K tiles past it are never loaded (block-sparse).
        q_hi = q_off + (j + 1) * block_q  # one past my last query position
        hi = jnp.clip(pl.cdiv(q_hi - k_off, block_k), 0, n_k)
    else:
        hi = n_k

    def body(kb, carry):
        acc, m, l = carry
        k_blk = k_ref[0, pl.ds(kb * block_k, block_k)].astype(jnp.float32)
        v_blk = v_ref[0, pl.ds(kb * block_k, block_k)]
        logits = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (BLOCK_Q, block_k)
        if causal:
            q_pos = q_off + j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 0
            )
            k_pos = k_off + kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 1
            )
            logits = jnp.where(q_pos >= k_pos, logits, NEG_INF)
        blk_m = jnp.max(logits, axis=-1)
        new_m = jnp.maximum(m, blk_m)
        # rows with nothing unmasked yet keep m = NEG_INF; exp(0)=1 terms
        # are zeroed by the logits <= NEG_INF/2 guard below
        p = jnp.exp(logits - new_m[:, None])
        p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
        alpha = jnp.exp(m - new_m)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_blk.dtype), v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, new_m, l

    d = q.shape[-1]
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    acc_ref[0] = acc
    # m/l are per-row scalars, but TPU output tiles need a lane dimension
    # that is 128-divisible (Mosaic rejects (1, block_q) blocks on real
    # hardware — caught on-chip, invisible in interpret mode). Broadcast
    # across a trailing _LANE-wide dim; the wrapper slices lane 0.
    m_ref[0] = jnp.broadcast_to(m[:, None], (block_q, _LANE))
    l_ref[0] = jnp.broadcast_to(l[:, None], (block_q, _LANE))


def attend_partials_einsum(q, k, v, q_offset, k_offset, causal):
    """Reference implementation of the blockwise-attend partials, in plain
    einsums: the off-TPU path, the interpret-mode oracle, AND the function
    whose vjp defines the kernel's backward (the kernel computes the exact
    same function, so the custom_vjp pairing is mathematically exact)."""
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum(
        'bqhd,bkhd->bhqk', q * scale, k, preferred_element_type=jnp.float32
    )
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        q_pos = q_offset + jnp.arange(sq)
        k_pos = k_offset + jnp.arange(sk)
        mask = q_pos[:, None] >= k_pos[None, :]
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    m = jnp.max(logits, axis=-1)  # (B,H,Q)
    p = jnp.exp(logits - m[..., None])
    # fully-masked rows: exp(NEG_INF - NEG_INF) = 1 would poison the sum
    p = jnp.where(logits <= NEG_INF / 2, 0.0, p)
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum(
        'bhqk,bkhd->bqhd', p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return acc, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _flash_partials(q, k, v, offs, causal, block_q, block_k, interpret):
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    n_q = s_q // block_q
    n_k = s_k // block_k
    kern = functools.partial(
        _flash_kernel, causal=causal, block_k=block_k, n_k=n_k
    )
    acc, m, l = _call(
        kern, offs, q, k, v, b, h, s_q, s_k, d, block_q, n_q, interpret
    )
    acc = acc.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
    # m/l carry a broadcast _LANE trailing dim (TPU tiling); lane 0 is the
    # value
    return acc, m[..., 0].reshape(b, h, s_q), l[..., 0].reshape(b, h, s_q)


def _flash_fwd(q, k, v, offs, causal, block_q, block_k, interpret):
    out = _flash_partials(q, k, v, offs, causal, block_q, block_k, interpret)
    return out, (q, k, v, offs)


def _flash_bwd(causal, block_q, block_k, interpret, res, cts):
    import numpy as np

    q, k, v, offs = res
    # backward through the mathematically-identical einsum implementation
    # (flash-backward kernels are the next optimization level; this keeps
    # the fused forward while autodiff stays exact)
    _, pull = jax.vjp(
        lambda q_, k_, v_: attend_partials_einsum(
            q_, k_, v_, offs[0], offs[1], causal
        ),
        q, k, v,
    )
    dq, dk, dv = pull(cts)
    return dq, dk, dv, np.zeros(offs.shape, jax.dtypes.float0)


_flash_partials.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_partials(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset=0,
    k_offset=0,
    causal: bool = True,
    block_q: int = BLOCK_Q,
    block_k: int = BLOCK_K,
    interpret: bool = False,
):
    """Blockwise-softmax partials of one (Q-chunk, K-chunk) attend.

    Args:
        q: (B, S_q, H, D); k, v: (B, S_k, H, D). S_q / S_k need not match
            (ring chunks). Sequence lengths must divide the block sizes
            (pad upstream; attention chunk sizes here are powers of two).
        q_offset / k_offset: global positions of the chunks' first rows
            (dynamic — ring steps pass axis-index-dependent values).
        causal: mask at global positions; K tiles wholly above the causal
            diagonal are skipped inside the kernel.

    Returns ``(acc, m, l)`` with shapes ((B, S_q, H, D) fp32, (B, H, S_q),
    (B, H, S_q)) — the same convention as models/attention._block_attend,
    mergeable with its ``_merge`` and normalized by ``_finish``.
    Differentiable: the backward runs the einsum implementation's vjp.
    """
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    if s_q % block_q or s_k % block_k:
        raise ValueError(
            f'sequence lengths ({s_q=}, {s_k=}) must divide the attention '
            f'blocks ({block_q=}, {block_k=})'
        )
    offs = jnp.asarray(
        [jnp.asarray(q_offset, jnp.int32), jnp.asarray(k_offset, jnp.int32)]
    )
    return _flash_partials(
        q, k, v, offs, causal, block_q, block_k, interpret
    )


def _call(kern, offs, q, k, v, b, h, s_q, s_k, d, block_q, n_q, interpret):
    from jax.experimental.pallas import tpu as pltpu

    def bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    # index maps receive the scalar-prefetch ref as a trailing argument
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b * h, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, offs: (i, j, 0)),
            pl.BlockSpec((1, s_k, d), lambda i, j, offs: (i, 0, 0)),
            pl.BlockSpec((1, s_k, d), lambda i, j, offs: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j, offs: (i, j, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda i, j, offs: (i, j, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda i, j, offs: (i, j, 0)),
        ],
    )
    # inside a vma-checked shard_map the outputs vary over the same mesh
    # axes as the (device-local) inputs
    vma = jax.typeof(q).vma

    def struct(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, vma=vma)

    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            struct((b * h, s_q, d)),
            struct((b * h, s_q, _LANE)),
            struct((b * h, s_q, _LANE)),
        ],
        interpret=interpret,
        name='_flash_kernel',
    )(offs, bh(q), bh(k), bh(v))


# the kernel stages the whole K and V chunks in VMEM (K/V BlockSpecs are
# (1, s_k, d)); cap their combined footprint well under the ~16 MB budget
# so long-context callers fall back instead of OOMing Mosaic. Ring/zigzag
# chunks shrink with the context-parallel world, so CP long-context runs
# stay under the cap by construction.
_VMEM_KV_BYTES = 8 * 1024 * 1024


# Where the dense path's floor came from: one 2026-07-31 chip session
# whose records are gone, never re-derived on the chip since (ROADMAP S6).
# - DENSE single-device attention competes against XLA's fused
#   softmax(QK^T)V: the flagship of that session ran slower with the
#   kernel at s=512, so the dense path only dispatches flash at
#   s_k >= 2048 where the S x S HBM materialization the kernel
#   eliminates is large.
# - The BLOCKWISE-PARTIALS form (ring/zigzag steps, the chunks of
#   blockwise_causal_attention) competes against attend_partials_einsum,
#   which must materialize unfused (acc, m, l) partials, so no length
#   floor applies there.
_MIN_FLASH_SK_DENSE = 2048


def use_flash_for(
    s_q: int, s_k: int, d: int, itemsize: int = 4, dense: bool = False
) -> bool:
    """Whether the flash kernel computes this attend, from what can be
    observed here: a TPU backend; whole lane-aligned tiles (``s_q``,
    ``s_k`` on their blocks, ``d`` on 128); the staged K+V chunks of
    ``itemsize`` bytes an element inside ``_VMEM_KV_BYTES``; a trace
    context GSPMD won't auto-partition
    (:func:`pallas_gate.mosaic_context_ok`); and on the single-device
    dense path (``dense=True``) ``s_k >= _MIN_FLASH_SK_DENSE``, because
    its alternative is XLA's fully-fused attention rather than the
    unfused einsum partials."""
    return (
        jax.default_backend() == 'tpu'
        and s_q % BLOCK_Q == 0
        and s_k % BLOCK_K == 0
        and (not dense or s_k >= _MIN_FLASH_SK_DENSE)
        and d % 128 == 0
        and 2 * s_k * d * itemsize <= _VMEM_KV_BYTES
        and pallas_gate.mosaic_context_ok()
    )
