"""Conv-hybrid sparse decoder operation count from the configuration:
matrix products of the projections, of the dense MLP, of the routed
experts by the rows routed to those held, of attention over the context,
and of the tied head. Embedding look-ups, norms, the short convolution's
three taps and two gates (elementwise) and the router's top-k are not
matrix products."""

from __future__ import annotations


def layer_types(config: dict) -> list:
    return [config['layer_types'][i] for i in config['source_layers']]


def matmul_params(config: dict) -> float:
    """Weights that multiply a token, summed over the layers: a conv
    mixer's four ``d x d`` projections, an attention mixer's q, k, v and o,
    the dense MLP's three in the leading dense layers, in the others the
    router and of the routed experts the expected share (a token's
    ``num_experts_per_tok`` choices fall on the ``experts_held[1]`` of
    ``router_width`` experts that live here with that probability each:
    the mean under even routing; the program's own count follows its
    routing), and the tied head."""
    c = config
    d = c['hidden_size']
    q = c['num_attention_heads'] * c['head_dim']
    kv = c['num_key_value_heads'] * c['head_dim']
    mixers = {'conv': 4 * d * d, 'full_attention': d * (q + 2 * kv) + q * d}
    rows_a_token = (
        c['num_experts_per_tok'] * c['experts_held'][1] / c['router_width']
    )
    moe = (
        d * c['router_width']
        + rows_a_token * 3 * d * c['moe_intermediate_size']
    )
    dense = 3 * d * c['intermediate_size']
    total = d * c['vocab_size']
    for i, kind in enumerate(layer_types(c)):
        total += mixers[kind] + (dense if i < c['num_dense_layers'] else moe)
    return total


def train_flops_per_token(config: dict) -> float:
    """Forward and backward: ``6 N`` for the weights and ``12 h d s`` an
    attention layer for the scores and their product with the values over
    a context of ``s`` (causal masking not discounted, as
    ``flops/lm.py``)."""
    c = config
    att = layer_types(c).count('full_attention')
    return (
        6 * matmul_params(c)
        + 12 * att * c['num_attention_heads'] * c['head_dim'] * c['seq_len']
    )


def train_flops_per_sample(config: dict) -> float:
    return config['seq_len'] * train_flops_per_token(config)
