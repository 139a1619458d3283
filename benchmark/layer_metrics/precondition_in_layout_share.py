"""Counter: the share of the preconditioned gradient's elements that the
engine multiplies in their parameter's own layout, in percent: Dense
kernels against their inverse slots as they lie, where every device holds
every inverse (``DistributedKFAC.in_layout_share``, counted once at
construction from the registry and the engine's resident layout). The rest
is packed into matrices or gradient stacks and unpacked again: the
convolutions' share, or everything where the decompositions are sharded by
column. ``None`` on a program whose engine does not report it."""


def read(ctx):
    share = getattr(ctx.run.trainer.kfac, 'in_layout_share', None)
    return None if share is None else 100.0 * share
