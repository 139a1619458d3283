"""Latent-attention sparse decoder operation count from the configuration:
matrix products of the mixer's projections (counted as the source's three
fused ones: the same columns), of the causal core with query-key heads
wider than the value heads, of the dense MLP, of the routed experts by the
rows routed to those held, of the shared experts and of the untied head.
Embedding look-ups, norms, the rotary parts' rotation and the router's
top-k are not matrix products."""

from __future__ import annotations


def dense_layers(config: dict) -> int:
    """How many of the run's layers carry the dense MLP: those of
    ``source_layers`` ahead of the source's ``first_k_dense_replace``."""
    return sum(
        i < config['first_k_dense_replace'] for i in config['source_layers']
    )


def matmul_params(config: dict) -> float:
    """Weights that multiply a token, summed over the layers: the mixer's
    ``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj`` and ``o_proj``; the
    dense MLP's three in the leading dense layers; in the others the
    router, the shared experts (every token) and of the routed experts the
    expected share (a token's ``num_experts_per_tok`` choices fall on the
    ``experts_held[1]`` of ``router_width`` experts that live here with
    that probability each: the mean under even routing); and the head."""
    c = config
    d, h = c['hidden_size'], c['num_attention_heads']
    qk = c['qk_nope_head_dim'] + c['qk_rope_head_dim']
    mixer = (
        d * h * qk
        + d * (c['kv_lora_rank'] + c['qk_rope_head_dim'])
        + c['kv_lora_rank'] * h * (c['qk_nope_head_dim'] + c['v_head_dim'])
        + h * c['v_head_dim'] * d
    )
    rows_a_token = (
        c['num_experts_per_tok'] * c['experts_held'][1] / c['router_width']
    )
    expert = 3 * d * c['moe_intermediate_size']
    moe = (
        d * c['router_width']
        + (rows_a_token + c['n_shared_experts']) * expert
    )
    dense = 3 * d * c['intermediate_size']
    layers, leading = len(c['source_layers']), dense_layers(c)
    return (
        d * c['vocab_size'] + layers * mixer
        + leading * dense + (layers - leading) * moe
    )


def core_flops_per_token(config: dict) -> float:
    """Forward and backward of one layer's causal core over a context of
    ``seq_len``: scores over the query-key head's width, their product
    with the values over the value head's (causal masking not discounted,
    as ``flops/lm.py``)."""
    c = config
    qk = c['qk_nope_head_dim'] + c['qk_rope_head_dim']
    return 6 * c['num_attention_heads'] * (qk + c['v_head_dim']) * c['seq_len']


def train_flops_per_token(config: dict) -> float:
    return 6 * matmul_params(config) + len(
        config['source_layers']
    ) * core_flops_per_token(config)


def train_flops_per_sample(config: dict) -> float:
    return config['seq_len'] * train_flops_per_token(config)
