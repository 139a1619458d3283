"""The benchmark's own weights: every leaf from the seed, in one jitted call.

The program's models give the *tree* (names and shapes, by
``jax.eval_shape`` of their ``init``); the *values* are made here, so the
plain reference and the program start from weights that neither of them
made. Rules are by leaf name, the last key of its path:

- ``kernel``: normal, std ``sqrt(2 / fan_in)`` for a convolution (4-D) and
  ``sqrt(1 / fan_in)`` for a dense layer (2-D);
- ``bias``: normal, std 0.02, and ``scale``: 1 + 0.1 normal — never exactly
  0 or 1, so no gradient leaf is identically zero (flax would zero-init the
  last BatchNorm scale of a bottleneck and with it a third of the
  convolutions' gradients);
- ``embedding`` and ``pos_embed``: normal, std 0.02 (GPT-2's);
- ``mean`` / ``var`` (BatchNorm running statistics): 0 / 1;
- anything else: normal, std 0.02.

All float32: the models cast to their compute type themselves.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (the driver's pass 2**31)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(
        jax.random.PRNGKey(int(words[0]) & 0x7FFFFFFF), int(words[1]) >> 1
    )


def _leaf(name: str, shape, key) -> jax.Array:
    normal = jax.random.normal(key, shape, jnp.float32)
    if name == 'kernel':
        fan_in = math.prod(shape[:-1])
        gain = 2.0 if len(shape) == 4 else 1.0
        return normal * math.sqrt(gain / fan_in)
    if name == 'scale':
        return 1.0 + 0.1 * normal
    if name == 'mean':
        return jnp.zeros(shape, jnp.float32)
    if name == 'var':
        return jnp.ones(shape, jnp.float32)
    return 0.02 * normal


def maker(shapes, sharding=None):
    """The jitted function that makes values for a tree of
    ``ShapeDtypeStruct`` leaves from a key. Kept and called again it
    builds no second program: the harness makes a run's weights anew
    inside the measured window, where none may be built.

    ``sharding`` (one for every leaf, e.g. replicated on the mesh) places
    the result where the trainer's state lives."""
    paths = jax.tree_util.tree_flatten_with_path(shapes)[0]
    treedef = jax.tree_util.tree_structure(shapes)

    def build(key):
        leaves = []
        for i, (path, leaf) in enumerate(paths):
            name = str(getattr(path[-1], 'key', path[-1]))
            leaves.append(_leaf(name, leaf.shape, jax.random.fold_in(key, i)))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=sharding)


def make(shapes, key, sharding=None):
    """Values for a tree of ``ShapeDtypeStruct`` leaves, from ``key``, in
    one jitted call: :func:`maker`'s function, called once."""
    return maker(shapes, sharding)(key)
