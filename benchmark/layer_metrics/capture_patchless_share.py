"""Counter: the share of the registered convolutions with a kernel larger
than 1 x 1 whose A factor is assembled from the activation's
autocorrelation, no patch row written, in percent (the engine's
``patchless_share``, counted once at construction from the registry:
stride 1, an odd kernel, padding that keeps the grid; the stem, the
stride-2 layers and ``VALID`` keep im2col). ``None`` on a program whose
engine does not report it, and where no such convolution is registered."""


def read(ctx):
    share = getattr(ctx.run.trainer.kfac, 'patchless_share', None)
    return None if share is None else 100.0 * share
