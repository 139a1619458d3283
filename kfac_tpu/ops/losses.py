"""Partition-friendly loss math.

The reference computes its LM loss as ``log_softmax`` + gather
(examples/torch_language_model.py criterion); that form is hostile to a
vocab-sharded head under GSPMD: ``take_along_axis`` over the sharded vocab
dimension lowers to an all-gather of the full logits. The fused form here
keeps every vocab-dimension operation a local-elementwise + reduction, so
when ``lm_head`` is sharded over the model axis (Megatron's
VocabParallelCrossEntropy, which the reference rides via its GPT-NeoX
integration) XLA partitions each token's loss as:

  local max  -> all-reduce max        (one scalar per token over tp ranks)
  local sum(exp(shifted))             -> all-reduce sum
  local masked target-logit sum       -> rides the same reduction

i.e. the d x V matmul AND the softmax stay 1/tp per device, and the only
cross-rank traffic is two (B, S) scalar reductions. With an unsharded head
the same code is just a fused, numerically-stable cross-entropy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from kfac_tpu import tracing


def vocab_parallel_nll(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token negative log-likelihood, safe for vocab-sharded logits.

    ``logits``: (..., V) — any dtype, reductions run in fp32; ``targets``:
    (...) int ids. Returns (...) fp32 NLLs. Numerically identical to
    ``-log_softmax(logits)[targets]`` (stable max-shift form), but written
    without a gather over the vocab axis: the target logit is extracted by
    a one-hot masked sum, which GSPMD partitions like any other vocab
    reduction instead of all-gathering the logits.

    The backward is the textbook ``softmax - one_hot`` (autodiff of this
    form produces exactly that), so gradients are partitioned the same way.
    """
    with tracing.model_scope('loss'):
        logits = logits.astype(jnp.float32)
        # stop_gradient: the max-shift is a numerical offset whose gradient
        # contributions cancel; detaching it saves the transpose ops.
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        shifted = logits - m
        onehot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
        # Both terms stay in shifted space (the m's cancel algebraically):
        # adding m back before subtracting would cost ~ulp(|m|) of absolute
        # precision at large logit magnitudes.
        lse_shifted = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
        target_shifted = jnp.sum(shifted * onehot, axis=-1)
        return lse_shifted - target_shifted
