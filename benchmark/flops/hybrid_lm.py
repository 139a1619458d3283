"""Sparse hybrid decoder operation count from the configuration: matrix
products of the projections, of the routed experts by the rows routed to
those held, of attention over the context, and of the chunked delta-rule
scan. Embedding look-ups, norms, the convolution of 4 taps, gates and the
router's top-k are not matrix products."""

from __future__ import annotations


def _attention_layers(config: dict) -> int:
    return config['num_hidden_layers'] // config['full_attention_interval']


def matmul_params(config: dict) -> float:
    """Weights that multiply a token, summed over the layers: every
    projection, the router, the shared expert and its gate, the head, and
    of the routed experts the expected share: a token's
    ``num_experts_per_tok`` choices fall on the ``experts_held[1]`` of
    ``router_width`` experts that live here with that probability each
    (the program's own count follows its routing; this is the mean under
    even routing)."""
    c = config
    d = c['hidden_size']
    kd = c['linear_num_key_heads'] * c['linear_key_head_dim']
    vd = c['linear_num_value_heads'] * c['linear_value_head_dim']
    deltanet = d * (2 * kd + 2 * vd + 2 * c['linear_num_value_heads']) + vd * d
    q = c['num_attention_heads'] * c['head_dim']
    kv = c['num_key_value_heads'] * c['head_dim']
    attention = d * (2 * q + 2 * kv) + q * d
    expert = 3 * d * c['moe_intermediate_size']
    rows_a_token = (
        c['num_experts_per_tok'] * c['experts_held'][1] / c['router_width']
    )
    moe = (
        d * c['router_width'] + 3 * d * c['shared_expert_intermediate_size']
        + d + rows_a_token * expert
    )
    layers, att = c['num_hidden_layers'], _attention_layers(c)
    return (
        (layers - att) * deltanet + att * attention + layers * moe
        + d * c['vocab_size']
    )


def scan_flops_per_token(config: dict) -> float:
    """The chunked delta rule's products a token and DeltaNet layer,
    forward: with chunks of ``C`` positions, a value head's ``C x C``
    products (``k k^T``: ``2 C d_k``; the unit triangular solve against
    ``d_v + d_k`` columns: ``C (d_v + d_k)``; ``q k^T``: ``2 C d_k``; its
    product with the updates: ``2 C d_v``) and its three ``C x d_k x d_v``
    products with the state (``6 d_k d_v``)."""
    c = config
    chunk = c.get('scan_chunk', 64)
    dk, dv = c['linear_key_head_dim'], c['linear_value_head_dim']
    head = (
        2 * chunk * dk + chunk * (dv + dk) + 2 * chunk * dk + 2 * chunk * dv
        + 6 * dk * dv
    )
    return c['linear_num_value_heads'] * head


def train_flops_per_token(config: dict) -> float:
    """Forward and backward: ``6 N`` for the weights, ``12 h d s`` an
    attention layer for the scores and their product with the values over
    a context of ``s`` (causal masking not discounted, as ``flops/lm.py``),
    and three times the scan's forward products a DeltaNet layer."""
    c = config
    att = _attention_layers(c)
    return (
        6 * matmul_params(c)
        + 12 * att * c['num_attention_heads'] * c['head_dim'] * c['seq_len']
        + 3 * (c['num_hidden_layers'] - att) * scan_flops_per_token(c)
    )


def train_flops_per_sample(config: dict) -> float:
    return config['seq_len'] * train_flops_per_token(config)
