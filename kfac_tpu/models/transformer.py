"""Decoder-only Transformer LM (the language-model family).

Parity target: the reference's Transformer LM example
(examples/torch_language_model.py, examples/language/transformer.py) which
trains a torch ``nn.TransformerEncoder`` LM and K-FAC-registers its dense
projections while skipping embedding/decoder/attention by default
(torch_language_model.py:163-168). This implementation is TPU-first:
pre-norm blocks, NHWC-free pure matmuls for the MXU, optional
``jax.checkpoint`` rematerialization, and attention projections expressed as
``nn.Dense`` so every projection (qkv, out, mlp) is a K-FAC layer.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfac_tpu import tracing
from kfac_tpu.models import moe as moe_lib
from kfac_tpu.ops import losses


class CausalSelfAttention(nn.Module):
    """Causal attention with optional context parallelism.

    With ``ring_mesh``/``ring_axis`` set, attention runs as ring attention
    over the sequence-sharded mesh axis (kfac_tpu/models/attention.py);
    otherwise a dense fused path is used.
    """

    num_heads: int
    dtype: Any = jnp.float32
    ring_mesh: Any = None
    ring_axis: str | None = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        from kfac_tpu.models import attention as attention_lib

        d = x.shape[-1]
        head_dim = d // self.num_heads
        q = nn.Dense(d, dtype=self.dtype, name='q_proj')(x)
        k = nn.Dense(d, dtype=self.dtype, name='k_proj')(x)
        v = nn.Dense(d, dtype=self.dtype, name='v_proj')(x)

        def split(t):
            return t.reshape(*t.shape[:-1], self.num_heads, head_dim)

        q, k, v = split(q), split(k), split(v)
        if self.ring_axis is not None:
            out = attention_lib.make_context_parallel_attention(
                self.ring_mesh, self.ring_axis, causal=True,
                num_heads=self.num_heads,
            )(q, k, v)
        else:
            out = attention_lib.dense_causal_attention(q, k, v)
        out = out.reshape(*x.shape[:-1], d)
        return nn.Dense(d, dtype=self.dtype, name='out_proj')(out)


class Block(nn.Module):
    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.float32
    ring_mesh: Any = None
    ring_axis: str | None = None
    num_experts: int = 0  # > 0 replaces the dense MLP with a switch MoE
    moe_capacity_factor: float | None = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        d = x.shape[-1]
        with tracing.model_scope('norm'):
            y = nn.LayerNorm(dtype=jnp.float32, name='ln1')(x)
        with tracing.model_scope('mixer'):
            x = x + CausalSelfAttention(
                self.num_heads, dtype=self.dtype, ring_mesh=self.ring_mesh,
                ring_axis=self.ring_axis, name='attn',
            )(y)
        with tracing.model_scope('norm'):
            y = nn.LayerNorm(dtype=jnp.float32, name='ln2')(x)
        if self.num_experts > 0:
            return x + moe_lib.MoEMLP(
                self.num_experts, self.mlp_ratio, dtype=self.dtype,
                capacity_factor=self.moe_capacity_factor,
                name='moe',
            )(y)
        with tracing.model_scope('mlp'):
            h = nn.Dense(self.mlp_ratio * d, dtype=self.dtype, name='mlp_up')(y)
            h = nn.gelu(h)
            x = x + nn.Dense(d, dtype=self.dtype, name='mlp_down')(h)
        return x


class TransformerLM(nn.Module):
    """GPT-style causal LM.

    Args mirror the reference example's surface
    (examples/torch_language_model.py:80-105: emsize/nhead/nhid/nlayers).
    """

    vocab_size: int = 32000
    d_model: int = 512
    num_heads: int = 8
    num_layers: int = 6
    mlp_ratio: int = 4
    max_len: int = 2048
    dtype: Any = jnp.float32
    remat: bool = False
    ring_mesh: Any = None
    ring_axis: str | None = None
    # switch-MoE (beyond the reference): every `moe_every`-th block uses
    # `num_experts` routed FFN experts instead of the dense MLP
    num_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float | None = None

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        seq = tokens.shape[-1]
        with tracing.model_scope('embed'):
            x = nn.Embed(self.vocab_size, self.d_model, name='embed')(tokens)
            pos = self.param(
                'pos_embed',
                nn.initializers.normal(0.02),
                (self.max_len, self.d_model),
            )
            x = (x + pos[:seq]).astype(self.dtype)
        block_cls = Block
        if self.remat:
            block_cls = nn.remat(Block)
        for i in range(self.num_layers):
            # moe_every <= 0 means no MoE blocks (same as num_experts=0)
            is_moe = (
                self.num_experts > 0
                and self.moe_every > 0
                and (i + 1) % self.moe_every == 0
            )
            x = block_cls(
                self.num_heads, self.mlp_ratio, dtype=self.dtype,
                ring_mesh=self.ring_mesh, ring_axis=self.ring_axis,
                num_experts=self.num_experts if is_moe else 0,
                moe_capacity_factor=self.moe_capacity_factor,
                name=f'block{i}',
            )(x)
        with tracing.model_scope('head'):
            x = nn.LayerNorm(dtype=jnp.float32, name='ln_f')(
                x.astype(jnp.float32)
            )
            return nn.Dense(self.vocab_size, use_bias=False, name='lm_head')(x)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """Zero-centred RMSNorm over the last axis, in float32: ``x *
    rsqrt(mean(x^2) + eps) * (1 + weight)``."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * (1.0 + weight)


class RMSNorm(nn.Module):
    """:func:`rms_norm` with its ``weight`` (no K-FAC layer's). Called with
    no input it returns the weight alone, for a caller that applies the
    norm inside a rematerialised function."""

    eps: float = 1e-6
    features: int | None = None

    @nn.compact
    def __call__(self, x: jax.Array | None = None) -> jax.Array:
        features = self.features if x is None else x.shape[-1]
        weight = self.param('weight', nn.initializers.zeros, (features,))
        return weight if x is None else rms_norm(x, weight, self.eps)


def rotary(x: jax.Array, rotary_dim: int, theta: float) -> jax.Array:
    """Rotary positions on the first ``rotary_dim`` of a head ``(B, S, H,
    D)``, in halves (``rotate_half``), positions ``0 .. S - 1``."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1
    )


def _gated_attend(
    q, k, v, gate, q_weight, k_weight, heads, kv_heads, head_dim,
    rotary_dim, theta, eps, chunk,
):
    """From the four projections' outputs to the output projection's
    input: QK-norm, rotary positions, blockwise causal attention and the
    sigmoid gate. It holds no parameter of a K-FAC layer, so it is
    rematerialised whole in the backward pass and keeps only its inputs."""
    from kfac_tpu.models import attention as attention_lib

    dtype = q.dtype

    def heads_of(t, n):
        return t.reshape(*t.shape[:-1], n, head_dim)

    with tracing.model_scope('attention'):
        q = rotary(
            rms_norm(heads_of(q, heads), q_weight, eps), rotary_dim, theta
        ).astype(dtype)
        k = rotary(
            rms_norm(heads_of(k, kv_heads), k_weight, eps), rotary_dim, theta
        ).astype(dtype)
        out = attention_lib.blockwise_causal_attention(
            q, k, heads_of(v, kv_heads), chunk
        )
        return out.reshape(gate.shape) * jax.nn.sigmoid(
            gate.astype(jnp.float32)
        ).astype(dtype)


class GatedAttention(nn.Module):
    """Grouped-query softmax attention with per-head QK-norm, rotary
    positions on part of the head and a sigmoid output gate:
    ``o_proj(attn * sigmoid(gate))``. Bias-free; ``q`` and ``gate`` are
    two projections (the source fuses them: same mathematics)."""

    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    rotary_dim: int = 64
    rope_theta: float = 1e7
    eps: float = 1e-6
    chunk: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim

        def dense(features, name):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype, name=name
            )

        out = jax.checkpoint(_gated_attend, static_argnums=tuple(range(6, 13)))(
            dense(h * hd, 'q_proj')(x), dense(hkv * hd, 'k_proj')(x),
            dense(hkv * hd, 'v_proj')(x), dense(h * hd, 'gate_proj')(x),
            RMSNorm(self.eps, hd, name='q_norm')(),
            RMSNorm(self.eps, hd, name='k_norm')(),
            h, hkv, hd, self.rotary_dim, self.rope_theta, self.eps, self.chunk,
        )
        return dense(x.shape[-1], 'o_proj')(out)


class HybridBlock(nn.Module):
    """``x += mixer(norm(x)); x += moe(norm(x))``: the mixer a
    :class:`GatedAttention` or a :class:`~kfac_tpu.models.deltanet
    .GatedDeltaNet`, the MLP a :class:`~kfac_tpu.models.moe.SparseMoE`."""

    make_mixer: Any  # name -> module
    make_moe: Any
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        with tracing.model_scope('norm'):
            y = RMSNorm(self.eps, name='norm1')(x)
        with tracing.model_scope('mixer'):
            x = x + self.make_mixer(name='mixer')(y).astype(x.dtype)
        with tracing.model_scope('norm'):
            y = RMSNorm(self.eps, name='norm2')(x)
        return x + self.make_moe(name='moe')(y).astype(x.dtype)


class HybridLM(nn.Module):
    """A sparse hybrid decoder (the Qwen3-Next family's shape): every
    ``full_attention_interval``-th layer gated softmax attention, the
    others Gated DeltaNet, each followed by top-k routed experts with a
    shared expert; zero-centred RMSNorm, no bias, no learned positions,
    an untied head. The residual stream is float32; projections compute
    in ``dtype``.

    ``experts_held = (first, count)``: the share of every layer's experts
    that lives here (``None``: all). ``models/moe.py`` ``SparseMoE`` has
    the semantics.
    """

    vocab_size: int = 32000
    d_model: int = 2048
    num_layers: int = 4
    full_attention_interval: int = 4
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel: int = 4
    num_experts: int = 512
    top_k: int = 10
    expert_width: int = 512
    shared_expert_width: int = 512
    experts_held: tuple[int, int] | None = None
    norm_topk_prob: bool = True
    rms_eps: float = 1e-6
    scan_chunk: int = 64
    attention_chunk: int = 1024
    expert_block_rows: int = 256
    loss_chunk: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, tokens: jax.Array, targets: jax.Array | None = None
    ) -> jax.Array:
        """Logits ``(B, S, V)``; with ``targets`` the per-token negative
        log-likelihood ``(B, S)`` instead, the head and the softmax taken a
        ``loss_chunk`` of positions at a time so that the float32
        logits of the whole batch never exist at once."""
        from kfac_tpu.models import deltanet

        with tracing.model_scope('embed'):
            x = nn.Embed(self.vocab_size, self.d_model, name='embed')(tokens)
            x = x.astype(jnp.float32)
        for i in range(self.num_layers):
            if (i + 1) % self.full_attention_interval == 0:
                mixer = functools.partial(
                    GatedAttention, self.num_heads, self.num_kv_heads, self.head_dim,
                    int(self.head_dim * self.partial_rotary_factor),
                    self.rope_theta, self.rms_eps, self.attention_chunk,
                    dtype=self.dtype,
                )
            else:
                mixer = functools.partial(
                    deltanet.GatedDeltaNet, self.linear_num_key_heads, self.linear_num_value_heads,
                    self.linear_key_head_dim, self.linear_value_head_dim,
                    self.linear_conv_kernel, self.scan_chunk, self.rms_eps,
                    dtype=self.dtype,
                )
            moe = functools.partial(
                moe_lib.SparseMoE, self.num_experts, self.top_k, self.expert_width,
                self.shared_expert_width, self.experts_held,
                self.norm_topk_prob, self.expert_block_rows,
                dtype=self.dtype,
            )
            x = HybridBlock(mixer, moe, self.rms_eps, name=f'block{i}')(x)
        with tracing.model_scope('head'):
            x = RMSNorm(self.rms_eps, name='norm_f')(x)
            head = nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.dtype,
                name='lm_head',
            )
            return head_or_nll(head, x, targets, self.loss_chunk)


def head_or_nll(head, x: jax.Array, targets: jax.Array | None, chunk: int):
    """``head(x)``, the logits ``(B, S, V)``; with ``targets`` every
    position's negative log-likelihood ``(B, S)`` instead, ``chunk``
    positions at a time (the whole sequence where it is no multiple), each
    chunk's softmax rematerialised: the logits are never held whole."""
    if targets is None:
        return head(x)
    seq = x.shape[1]
    step = chunk if seq % chunk == 0 else seq
    nll = jax.checkpoint(losses.vocab_parallel_nll)
    return jnp.concatenate([
        nll(head(x[:, i:i + step]), targets[:, i:i + step])
        for i in range(0, seq, step)
    ], axis=1)


def lm_loss(model: TransformerLM):
    """Next-token cross-entropy: loss_fn(params, (tokens, targets))."""

    def loss_fn(params, batch):
        tokens, targets = batch
        logits = model.apply({'params': params}, tokens)
        # fused NLL: no gather over the vocab axis, so a TP-sharded lm_head
        # (TRANSFORMER_TP_RULES marks it vocab-parallel) keeps the matmul
        # and softmax 1/tp per device (ops/losses.vocab_parallel_nll)
        with tracing.model_scope('loss'):
            return jnp.mean(losses.vocab_parallel_nll(logits, targets))

    return loss_fn


def hybrid_lm_loss(model: HybridLM):
    """:func:`lm_loss` for a :class:`HybridLM`, which is called with the
    targets and returns every position's loss itself (its head's softmax
    runs in chunks of positions and the logits are never held whole)."""

    def loss_fn(params, batch):
        tokens, targets = batch
        nll = model.apply({'params': params}, tokens, targets)
        with tracing.model_scope('loss'):
            return jnp.mean(nll)

    return loss_fn
