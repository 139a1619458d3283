"""Per-layer metrics, one reader per metric, found by the metric's name in
``BENCHMARK.json``: ``<name>.py`` exposes ``read(ctx) -> float | None``
(``ctx``: ``benchmark.harness.LayerContext``), or a row ``<name>.json``
asks for device milliseconds per step of a kind (``"per"``: a step kind,
or null for every step) under ``jax.named_scope`` names:
``{"scopes": [...], "per": ...}``; or a row ``{"reads": "<metric>"}`` is
that metric's reader under another name, for the cells that report
another end-to-end metric than the one ``<metric>`` moves (a traced run
reads the rows whose ``moves`` its cell reports: ``harness.layer_rows``).
A reader that finds nothing to read returns ``None`` and the metric is
left out of the line. A module that says ``AFTER_FIRST_ORDER = True`` is
read once the first-order stretch has run and the K-FAC trainer's state is
gone; every other while that state is on the device
(``harness.LayerContext``).
"""
