"""Counter: GB of K-FAC factors and inverses that belong to the blocks'
token mixers (``DistributedKFAC.state_bytes_by_part['mixer']``, counted
once at construction from the registry's layers and A groups: a square a
side for the factor and one for its inverse, an A group's once, slot
padding not counted). ``None`` on an engine without the counter, or on a
model whose layers lie under no ``mixer``."""


def read(ctx):
    parts = getattr(ctx.run.trainer.kfac, 'state_bytes_by_part', None)
    if not parts or 'mixer' not in parts:
        return None
    return parts['mixer'] / 1e9
