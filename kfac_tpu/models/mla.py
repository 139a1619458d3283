"""A latent-attention sparse decoder (the DeepSeek-V3 family's shape).

Blocks and feed-forward parts are ``models/conv_moe.py``'s: ``h = x +
mixer(norm(x)); x' = h + ffn(norm(h))`` with a plain RMSNorm, a dense
gated MLP in the first ``num_dense_layers`` blocks and sigmoid-routed
experts with a selection bias after. What differs:

- the mixer is multi-head latent attention (:class:`LatentAttention`):
  keys and values come out of a normed low-rank latent, a query-key head
  is a part without positions (``qk_nope_head_dim``) beside a rotary part
  (``qk_rope_head_dim``), and the rotary key is one head shared by all;
- the routed weights are scaled (``routed_scale``) and the shared
  experts, one gated MLP ``num_shared_experts * expert_width`` wide, are
  added with no gate;
- the head ``lm_head`` is untied.

The source fuses the mixer's projections into three (``q_proj``,
``kv_a_proj_with_mqa``, ``kv_b_proj``); they are declared here as six, a
column map apart (:func:`fused_kernels`): same mathematics, no K-FAC
factor wider than the widest of them.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from kfac_tpu import tracing
from kfac_tpu.models import attention as attention_lib
from kfac_tpu.models import conv_moe
from kfac_tpu.models import moe as moe_lib
from kfac_tpu.models import transformer


def _deinterleave(x: jax.Array) -> jax.Array:
    """``(x0, x1, x2, x3, ...)`` -> ``(x0, x2, ... | x1, x3, ...)``: the
    source rotates the pairs ``(2j, 2j + 1)`` (``rope_interleave``), which
    is the rotation in halves of this order."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _latent_attend(q_nope, q_rope, k_nope, k_rope, v, heads, theta, chunk):
    """From the projections' outputs to the output projection's input:
    rotary positions on the rotary parts (the key's one head for all),
    the heads put together, blockwise causal attention with query-key
    heads wider than the value heads. It holds no parameter, so it is
    rematerialised whole in the backward pass (``conv_moe._attend``)."""
    dtype = q_nope.dtype
    rope = k_rope.shape[-1]

    def heads_of(t):
        return t.reshape(*t.shape[:-1], heads, t.shape[-1] // heads)

    def rotated(t):
        return transformer.rotary(
            _deinterleave(t.astype(jnp.float32)), rope, theta
        ).astype(dtype)

    with tracing.model_scope('mla_latent'):
        k_r = rotated(k_rope[..., None, :])
        q = jnp.concatenate(
            [heads_of(q_nope), rotated(heads_of(q_rope))], axis=-1
        )
        k = jnp.concatenate([
            heads_of(k_nope),
            jnp.broadcast_to(k_r, (*k_r.shape[:2], heads, rope)),
        ], axis=-1)
    with tracing.model_scope('attention'):
        out = attention_lib.blockwise_causal_attention(
            q, k, heads_of(v), chunk
        )
        return out.reshape(*out.shape[:-2], v.shape[-1])


class LatentAttention(nn.Module):
    """Multi-head latent attention without a query latent (``q_lora_rank``
    null): ``o_proj(attn(q, k, v))`` with, a head, ``q = [q_nope |
    rot(q_rope)]``, ``k = [k_nope | rot(k_rope)]`` (``k_rope`` one head
    for all) and ``[k_nope | v]`` two projections of
    ``kv_a_layernorm(kv_a_proj(u))``. Scores over ``sqrt(qk_nope_head_dim
    + qk_rope_head_dim)``. Bias-free.

    The four projections of ``u`` are handed one array and the two of the
    normed latent one array, so K-FAC keeps one A factor for each group
    (``Registry.a_groups``)."""

    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 1e6
    eps: float = 1e-6
    chunk: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        h = self.num_heads

        def dense(features, name):
            return nn.Dense(
                features, use_bias=False, dtype=self.dtype, name=name
            )

        q_nope = dense(h * self.qk_nope_head_dim, 'q_nope_proj')(u)
        q_rope = dense(h * self.qk_rope_head_dim, 'q_rope_proj')(u)
        latent = dense(self.kv_lora_rank, 'kv_a_proj')(u)
        k_rope = dense(self.qk_rope_head_dim, 'k_rope_proj')(u)
        # outside the rematerialised part: it feeds K-FAC layers
        latent = conv_moe.PlainRMSNorm(self.eps, name='kv_a_layernorm')(latent)
        out = jax.checkpoint(_latent_attend, static_argnums=(5, 6, 7))(
            q_nope, q_rope,
            dense(h * self.qk_nope_head_dim, 'k_nope_proj')(latent), k_rope,
            dense(h * self.v_head_dim, 'v_proj')(latent),
            h, self.rope_theta, self.chunk,
        )
        return dense(u.shape[-1], 'o_proj')(out)


def fused_kernels(mixer: dict, heads: int) -> dict:
    """The source's three fused kernels from a :class:`LatentAttention`'s
    parameters, the column map of the declaration: ``q_proj`` a head at a
    time ``[q_nope | q_rope]``, ``kv_a_proj_with_mqa`` ``[latent |
    k_rope]``, ``kv_b_proj`` a head at a time ``[k_nope | v]``."""

    def by_head(*names):
        parts = [mixer[n]['kernel'] for n in names]
        return jnp.concatenate([
            p.reshape(p.shape[0], heads, -1) for p in parts
        ], axis=-1).reshape(parts[0].shape[0], -1)

    return {
        'q_proj': by_head('q_nope_proj', 'q_rope_proj'),
        'kv_a_proj_with_mqa': jnp.concatenate([
            mixer['kv_a_proj']['kernel'], mixer['k_rope_proj']['kernel']
        ], axis=-1),
        'kv_b_proj': by_head('k_nope_proj', 'v_proj'),
    }


class LatentMoELM(nn.Module):
    """The decoder the module describes. The first ``num_dense_layers``
    blocks carry a dense gated MLP ``dense_width`` wide, the others
    ``num_experts`` routed experts ``expert_width`` wide, of which
    ``experts_held = (first, count)`` live here (``None``: all;
    ``models/moe.py`` ``SparseMoE`` has the semantics), beside
    ``num_shared_experts`` shared ones."""

    vocab_size: int = 128256
    d_model: int = 2048
    num_layers: int = 48
    num_dense_layers: int = 1
    dense_width: int = 6144
    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 1e6
    num_experts: int = 128
    top_k: int = 6
    expert_width: int = 768
    num_shared_experts: int = 2
    experts_held: tuple[int, int] | None = None
    norm_topk_prob: bool = True
    routed_scale: float = 2.448
    norm_eps: float = 1e-6
    attention_chunk: int = 1024
    expert_block_rows: int = 256
    loss_chunk: int = 1024
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(
        self, tokens: jax.Array, targets: jax.Array | None = None
    ) -> jax.Array:
        """Logits ``(B, S, V)``; with ``targets`` the per-token negative
        log-likelihood ``(B, S)`` instead (``transformer.head_or_nll``)."""
        with tracing.model_scope('embed'):
            x = nn.Embed(self.vocab_size, self.d_model, name='embed')(
                tokens
            ).astype(jnp.float32)
        mixer = functools.partial(
            LatentAttention, self.num_heads, self.qk_nope_head_dim,
            self.qk_rope_head_dim, self.v_head_dim, self.kv_lora_rank,
            self.rope_theta, self.norm_eps, self.attention_chunk,
            dtype=self.dtype,
        )
        for i in range(self.num_layers):
            if i < self.num_dense_layers:
                ffn = functools.partial(
                    moe_lib.GatedMLP, self.dense_width, dtype=self.dtype,
                    name='mlp',
                )
            else:
                ffn = functools.partial(
                    moe_lib.SparseMoE, self.num_experts, self.top_k,
                    self.expert_width,
                    self.num_shared_experts * self.expert_width,
                    self.experts_held, self.norm_topk_prob,
                    self.expert_block_rows, dtype=self.dtype,
                    scoring='sigmoid', selection_bias=True, renorm_eps=1e-20,
                    routed_scale=self.routed_scale, shared_gated=False,
                    name='moe',
                )
            x = conv_moe.ConvMoEBlock(
                mixer, ffn, self.norm_eps, name=f'block{i}'
            )(x)
        with tracing.model_scope('head'):
            x = conv_moe.PlainRMSNorm(self.norm_eps, name='norm_f')(x)
            head = nn.Dense(
                self.vocab_size, use_bias=False, dtype=self.dtype,
                name='lm_head',
            )
            return transformer.head_or_nll(head, x, targets, self.loss_chunk)
