"""Counter: the share of the model's parameters that K-FAC does not
precondition, in percent: the leaves ``register_model`` reports as passed
through (embedding, norm weights, depthwise kernels, a router's selection
bias) or skipped (``skip_layers``: here a dense MLP too wide to factor
whole), by their element counts, over all parameters. They take the
optimizer's first-order update. ``None`` where the registry reports no
pass-through leaves."""

import math

import jax


def read(ctx):
    job = ctx.run.job
    passed = getattr(job.registry, 'passthrough', None)
    if not passed:
        return None
    flat = jax.tree_util.tree_flatten_with_path(job.variable_shapes['params'])
    sizes = {
        '/'.join(str(getattr(k, 'key', k)) for k in path): math.prod(leaf.shape)
        for path, leaf in flat[0]
    }
    return 100.0 * sum(sizes[leaf] for leaf in passed) / sum(sizes.values())
