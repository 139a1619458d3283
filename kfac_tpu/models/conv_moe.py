"""A conv-hybrid sparse decoder (the LFM2-MoE family's shape).

Each block is ``h = x + op(norm(x)); x' = h + ffn(norm(h))`` with a plain
RMSNorm (``x rsqrt(mean x^2 + eps) w``, ``w`` around 1). ``op`` is, by the
layer's entry in ``layer_types``:

- ``'conv'``, a gated short convolution (:class:`ShortConv`): three
  projections of the normed input, ``B``, ``C`` and ``x~``; ``z = B * x~``;
  a depthwise causal convolution of ``L`` taps over positions, zeros left
  of the sequence, no bias; ``y = C * conv(z)``; an output projection. No
  activation and no recurrent state. (The source fuses the three into one
  ``in_proj`` chunked in that order: same mathematics.)
- ``'full_attention'``, grouped-query softmax attention
  (:class:`GroupedQueryAttention`) with a per-head RMSNorm on ``q`` and
  ``k``, rotary positions on the whole head and no output gate.

``ffn`` is a dense gated MLP in the first ``num_dense_layers`` blocks and
afterwards :class:`~kfac_tpu.models.moe.SparseMoE` with sigmoid scores, a
selection-only ``expert_bias`` and no shared expert. The head is the
embedding, tied. The residual stream, the norms, the router and the
convolution's products are float32; projections compute in ``dtype``.

What K-FAC does not factor here (``layers/registry.py`` pass-through
rule): the embedding, the norm weights, the depthwise kernels and
``expert_bias``. Every projection is an ordinary bias-free ``nn.Dense``
(the held experts' stacked, as ``models/moe.py`` has them).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfac_tpu import tracing
from kfac_tpu.models import attention as attention_lib
from kfac_tpu.models import moe as moe_lib
from kfac_tpu.models import transformer

LAYER_TYPES = ('conv', 'full_attention')


def plain_rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis, in float32: ``x * rsqrt(mean(x^2) + eps)
    * scale`` (not the zero-centred ``1 + weight`` of
    :func:`~kfac_tpu.models.transformer.rms_norm`)."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps
    ) * scale


class PlainRMSNorm(nn.Module):
    """:func:`plain_rms_norm` with its ``scale`` (no K-FAC layer's). Called
    with no input it returns the scale alone, for a caller that applies
    the norm inside a rematerialised function."""

    eps: float = 1e-5
    features: int | None = None

    @nn.compact
    def __call__(self, x: jax.Array | None = None) -> jax.Array:
        features = self.features if x is None else x.shape[-1]
        scale = self.param('scale', nn.initializers.ones, (features,))
        return scale if x is None else plain_rms_norm(x, scale, self.eps)


def short_conv(b: jax.Array, c: jax.Array, x: jax.Array, kernel: jax.Array):
    """``C * conv(B * x~)`` in float32: ``kernel`` is ``(L, channels)``,
    ``conv(z)_t = sum_j kernel[j] * z_{t - (L - 1) + j}`` with zeros left
    of the sequence. ``(B, T, channels)`` in, the same shape out in
    ``b.dtype``. It holds no parameter of a K-FAC layer, so a caller
    rematerialises it whole and keeps only its inputs."""
    taps, t = kernel.shape[0], x.shape[1]
    with tracing.model_scope('short_conv'):
        z = b.astype(jnp.float32) * x.astype(jnp.float32)
        zp = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(
            zp[:, j:j + t] * kernel[j].astype(jnp.float32)
            for j in range(taps)
        )
        return (c.astype(jnp.float32) * conv).astype(b.dtype)


class _DepthwiseKernel(nn.Module):
    """A depthwise convolution's ``kernel`` leaf ``(L, 1, channels)``, as
    :class:`~kfac_tpu.models.deltanet.CausalConv1d` declares it; returned
    as ``(L, channels)``."""

    taps: int
    channels: int

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param(
            'kernel', nn.initializers.lecun_normal(),
            (self.taps, 1, self.channels),
        )[:, 0]


class ShortConv(nn.Module):
    """The gated short convolution mixer: ``out_proj(C * conv(B * x~))``,
    ``B``, ``C`` and ``x~`` three projections of the input, the depthwise
    kernel ``conv/kernel``."""

    kernel_size: int = 3
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        d = u.shape[-1]

        def dense(name):
            return nn.Dense(d, use_bias=False, dtype=self.dtype, name=name)

        y = jax.checkpoint(short_conv)(
            dense('b_proj')(u), dense('c_proj')(u), dense('x_proj')(u),
            _DepthwiseKernel(self.kernel_size, d, name='conv')(),
        )
        return dense('out_proj')(y)


def _attend(q, k, v, q_scale, k_scale, heads, kv_heads, head_dim, theta, eps,
            chunk):
    """From the three projections' outputs to the output projection's
    input: QK-norm, rotary positions on the whole head, blockwise causal
    attention. Rematerialised whole in the backward pass, as
    ``transformer._gated_attend`` is."""
    dtype = q.dtype

    def heads_of(t, n):
        return t.reshape(*t.shape[:-1], n, head_dim)

    with tracing.model_scope('attention'):
        q = transformer.rotary(
            plain_rms_norm(heads_of(q, heads), q_scale, eps), head_dim, theta
        ).astype(dtype)
        k = transformer.rotary(
            plain_rms_norm(heads_of(k, kv_heads), k_scale, eps), head_dim,
            theta,
        ).astype(dtype)
        out = attention_lib.blockwise_causal_attention(
            q, k, heads_of(v, kv_heads), chunk
        )
        return out.reshape(*out.shape[:-2], heads * head_dim)


class GroupedQueryAttention(nn.Module):
    """Grouped-query softmax attention with a per-head RMSNorm on ``q``
    and ``k`` (``q_layernorm``, ``k_layernorm``), rotary positions on the
    whole head and no gate: ``o_proj(attn)``. Bias-free."""

    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    eps: float = 1e-5
    chunk: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        h, hkv, hd = self.num_heads, self.num_kv_heads, self.head_dim

        def dense(features, name):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype, name=name
            )

        out = jax.checkpoint(_attend, static_argnums=tuple(range(5, 11)))(
            dense(h * hd, 'q_proj')(u), dense(hkv * hd, 'k_proj')(u),
            dense(hkv * hd, 'v_proj')(u),
            PlainRMSNorm(self.eps, hd, name='q_layernorm')(),
            PlainRMSNorm(self.eps, hd, name='k_layernorm')(),
            h, hkv, hd, self.rope_theta, self.eps, self.chunk,
        )
        return dense(u.shape[-1], 'o_proj')(out)


class ConvMoEBlock(nn.Module):
    """``h = x + mixer(norm1(x)); x' = h + ffn(norm2(h))``; the feed-forward
    part brings its own name: ``mlp`` where it is dense, ``moe`` where it
    is routed."""

    make_mixer: Any  # name -> module
    make_ffn: Any    # () -> module
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        with tracing.model_scope('norm'):
            y = PlainRMSNorm(self.eps, name='norm1')(x)
        with tracing.model_scope('mixer'):
            x = x + self.make_mixer(name='mixer')(y).astype(x.dtype)
        with tracing.model_scope('norm'):
            y = PlainRMSNorm(self.eps, name='norm2')(x)
        return x + self.make_ffn()(y).astype(x.dtype)


class ConvMoELM(nn.Module):
    """The decoder the module describes. ``layer_types[i]`` is block
    ``i``'s mixer; the first ``num_dense_layers`` blocks carry a dense
    gated MLP ``dense_width`` wide, the others ``num_experts`` routed
    experts ``expert_width`` wide of which ``experts_held = (first,
    count)`` live here (``None``: all; ``models/moe.py`` ``SparseMoE`` has
    the semantics).
    """

    vocab_size: int = 65536
    d_model: int = 2048
    layer_types: tuple[str, ...] = ('conv', 'conv', 'full_attention', 'conv')
    num_dense_layers: int = 2
    dense_width: int = 11776
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    conv_kernel: int = 3
    num_experts: int = 64
    top_k: int = 4
    expert_width: int = 1536
    experts_held: tuple[int, int] | None = None
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    norm_eps: float = 1e-5
    attention_chunk: int = 1024
    expert_block_rows: int = 256
    loss_chunk: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, tokens: jax.Array, targets: jax.Array | None = None
    ) -> jax.Array:
        """Logits ``(B, S, V)``; with ``targets`` the per-token negative
        log-likelihood ``(B, S)`` instead, a ``loss_chunk`` of positions at
        a time (as :class:`~kfac_tpu.models.transformer.HybridLM`)."""
        unknown = set(self.layer_types) - set(LAYER_TYPES)
        if unknown:
            raise ValueError(f'layer types {sorted(unknown)}: not {LAYER_TYPES}')
        embed = nn.Embed(self.vocab_size, self.d_model, name='embed')
        with tracing.model_scope('embed'):
            x = embed(tokens).astype(jnp.float32)
        for i, kind in enumerate(self.layer_types):
            if kind == 'conv':
                mixer = functools.partial(
                    ShortConv, self.conv_kernel, dtype=self.dtype
                )
            else:
                mixer = functools.partial(
                    GroupedQueryAttention, self.num_heads, self.num_kv_heads,
                    self.head_dim, self.rope_theta, self.norm_eps,
                    self.attention_chunk, dtype=self.dtype,
                )
            if i < self.num_dense_layers:
                ffn = functools.partial(
                    moe_lib.GatedMLP, self.dense_width, dtype=self.dtype,
                    name='mlp',
                )
            else:
                ffn = functools.partial(
                    moe_lib.SparseMoE, self.num_experts, self.top_k,
                    self.expert_width, 0, self.experts_held,
                    self.norm_topk_prob, self.expert_block_rows,
                    dtype=self.dtype, scoring='sigmoid',
                    selection_bias=self.use_expert_bias, renorm_eps=1e-6,
                    name='moe',
                )
            x = ConvMoEBlock(mixer, ffn, self.norm_eps, name=f'block{i}')(x)
        with tracing.model_scope('head'):
            x = PlainRMSNorm(self.norm_eps, name='norm_f')(x)
            table = embed.embedding.astype(self.dtype)

            def head(x):
                return jnp.dot(x.astype(self.dtype), table.T)

            return transformer.head_or_nll(head, x, targets, self.loss_chunk)
