"""Test configuration: force an 8-device virtual CPU mesh.

The reference simulates clusters by forking gloo process groups
(testing/distributed.py:24-141). The JAX equivalent is a host-platform
device-count override: the same SPMD program that runs on a TPU pod runs on
8 virtual CPU devices, so every sharding/collective path is exercised
in-process. This must happen before the first JAX backend initialization.

The platform is pinned to the CPU here as well as in the tier-1 command,
so a bare ``pytest`` on a machine with a chip still runs the suite on
the virtual mesh.
"""

import os

flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8'
    )

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')
jax.config.update('jax_enable_x64', False)

# Persistent compilation cache: the container has ONE cpu core, so the
# suite's wall-clock is almost entirely XLA compiles (measured r2: 51:47).
# Caching compiled executables across runs cuts repeat suites to minutes —
# a suite fast enough to actually run before every commit (the reference's
# 15-minute CI budget, BASELINE.md). The cache dir follows the one rule
# in kfac_tpu/utils/compile_cache.py (JAX_COMPILATION_CACHE_DIR if set,
# else the git-ignored <checkout>/.jax_cache). The cpu_aot_loader "machine feature" stderr noise on cache
# hits refers to XLA preference flags (prefer-no-scatter/gather), not host
# ISA — harmless.
from kfac_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()
# min_compile_time 0: with the per-module clear_caches below, even
# sub-second programs re-JIT once per module — serve them from disk too.
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope='module')
def _clear_jax_caches_per_module():
    """Drop in-memory compiled executables after each test module.

    The full suite accumulates every module's jitted programs (~49 GB RSS
    observed at the pipeline tests, round 4), and the resulting memory
    pressure inflated individual tests 3-4x over their isolated times
    (e.g. zigzag gradients: 133 s in-suite vs 37 s isolated). Modules
    don't share programs, and re-JITs after a clear are served by the
    persistent on-disk cache, so clearing at module teardown trades a
    little deserialization for a bounded working set.
    """
    yield
    jax.clear_caches()
