"""Plain references: what each configuration computes, in straightforward
``jax.numpy`` float32 under ``Precision.HIGHEST``, with nothing of the
program imported. ``benchmark/refs/<kind>.py`` is the model of a kind;
``kfac.py`` is the K-FAC step by the documented formulas.
"""
