"""Trace: device time under scope ``model.mla_latent`` per traced step:
latent attention's glue between its projections and the causal core (the
rotary parts de-interleaved and rotated, the one rotary key head broadcast
over the heads, a head's two parts put together), forward, rematerialised
forward and backward. It is a *part* of ``dev_ms.mixer``: the step map
(``_stepmap.py``) does not know the scope and gives these operations to
``model.mixer``, the scope around them, so its partition adds up as it
did. A fusion carries its root instruction's name: what the compiler makes
an epilogue of a projection's product is that product's and is not read
here. ``None`` on a program without the scope."""

from benchmark.layer_metrics import _hybrid

MLA_LATENT = 'model.mla_latent'


def read(ctx):
    return _hybrid.scope_ms(ctx, (MLA_LATENT,), among=(MLA_LATENT,))
