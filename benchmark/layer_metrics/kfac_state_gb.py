"""Counter: bytes of K-FAC state (factors and inverses) on the fullest
device, from the state's own shards."""

import jax


def read(ctx):
    per_device = {}
    for leaf in jax.tree_util.tree_leaves(ctx.run.state.kfac_state):
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] = (
                per_device.get(shard.device.id, 0) + shard.data.nbytes
            )
    return max(per_device.values()) / 1e9 if per_device else None
