"""Decoder-only transformer operation count from the configuration."""

from __future__ import annotations


def matmul_params(config: dict) -> int:
    """Weights that multiply every token: the blocks' projections and the
    output head. Embedding look-ups, biases and LayerNorms are not
    matrix multiplications."""
    m = config['model']
    d, inner = m['n_embd'], m['mlp_ratio'] * m['n_embd']
    block = 4 * d * d + 2 * d * inner
    return m['n_layer'] * block + d * m['vocab_size']


def train_flops_per_token(config: dict) -> int:
    """``6 N + 12 L d s``: six operations a weight a token for forward and
    backward, and the attention scores and their product with the values
    over a context of ``s`` (causal masking not discounted, as is usual)."""
    m = config['model']
    return 6 * matmul_params(config) + (
        12 * m['n_layer'] * m['n_embd'] * config['seq_len']
    )


def train_flops_per_sample(config: dict) -> int:
    return config['seq_len'] * train_flops_per_token(config)
