"""Per-layer metrics, one reader per metric, found by the metric's name in
``BENCHMARK.json``: ``<name>.py`` exposes ``read(ctx) -> float | None``
(``ctx``: ``benchmark.harness.LayerContext``), or a row ``<name>.json``
asks for device milliseconds per step of a kind (``"per"``: a step kind,
or null for every step): ``{"scopes": [...], "per": ...}`` under those
``jax.named_scope`` names, ``{"ops": [...], "per": ...}`` in kernels of
those names. A reader that finds nothing to read returns ``None`` and the
metric is left out of the line.
"""
