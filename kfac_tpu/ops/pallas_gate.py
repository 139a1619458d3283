"""What both Pallas TPU kernel families ask of the trace they are in.

Which kernel runs is decided in the kernel's own module, from what it
can observe: ``pallas_attention.use_flash_for`` and
``pallas_ns.use_fused_klclip_for`` (backend, shape, dtype, a constant of
their own). This module holds the questions the two share: whether a raw
Mosaic call may run in the current trace context
(:func:`mosaic_context_ok`), and whether the kernels run in the Pallas
interpreter (:func:`interpret_mode`). The kl-clip pair was measured
against its XLA expressions on a v5e and lost at every shape, so nothing
dispatches it (``PERF.md`` section 6, PR 37); the flash partials' speed
against the einsum path has not been measured on today's code
(ROADMAP D5). What IS checked on the chip is that every kernel compiles
under Mosaic and agrees with the XLA expression it stands for
(``chip_smoke.py``).
"""

from __future__ import annotations


def interpret_mode() -> bool:
    """Run the kernels in interpret mode off-TPU (tests, CPU meshes).

    Every Pallas family routes through this, so off a TPU the kernels
    silently become the Pallas interpreter — right for tests, and never
    a measurement. Nothing here proves Mosaic compiled anything:
    ``chip_smoke.py`` does, by refusing to run unless
    ``jax.devices()[0].platform == 'tpu'`` and by listing the
    ``tpu_custom_call`` kernels found in the compiled step programs.
    """
    import jax

    return jax.default_backend() != 'tpu'


def manual_context() -> tuple[bool, bool, bool]:
    """``(has_mesh, any_manual, all_manual)`` for the current trace context.

    The single source of truth for whether a raw ``pallas_call`` may run
    here (Mosaic kernels cannot be automatically partitioned). Inside
    shard_map regions — ``check_vma=True`` or ``False`` — the abstract
    mesh's ``axis_types`` carries ``Manual`` for exactly the manual
    axes; aval ``vma`` is NOT a reliable signal (empty under
    ``check_vma=False``), so axis types alone decide.
    """
    import jax

    am = jax.sharding.get_abstract_mesh()
    manual = [t == jax.sharding.AxisType.Manual for t in am.axis_types]
    return bool(am.axis_names), any(manual), bool(manual) and all(manual)


def mosaic_context_ok() -> bool:
    """Whether the current trace context can execute a raw ``pallas_call``.

    Mosaic kernels cannot be automatically partitioned (measured on-chip:
    ``NotImplementedError: Mosaic kernels cannot be automatically
    partitioned`` from a flash dispatch inside the pipeline's
    partial shard_map, whose model axis stays automatic). Safe contexts:

    - a FULLY-manual shard_map region: every mesh axis manual, so the
      kernel sees device-local blocks and GSPMD never touches it;
    - no surrounding mesh AND a single-device process: with more than
      one device, inputs placed via ``device_put(NamedSharding)`` can
      arrive sharded without any mesh context and would still need GSPMD
      to partition the kernel.

    Partial-manual regions (pipeline manual over pipe+data with TP
    automatic) and plain pjit meshes fall back to the XLA expressions,
    which XLA partitions fine.
    """
    import jax

    has_mesh, _any_manual, all_manual = manual_context()
    if has_mesh:
        return all_manual
    return len(jax.devices()) == 1
