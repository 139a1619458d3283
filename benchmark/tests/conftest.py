"""The benchmark's CPU tests: the arithmetic by import, a tiny cell end to
end through ``harness.run_cell`` (the chip check is ``run.py``'s, and is
skipped). They touch no TPU topology, start no process and print no device
number. Run them with ``python -m pytest benchmark/tests -q``.
"""

import os
import sys

flags = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8'
    )
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
))))

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

from kfac_tpu.utils import compile_cache  # noqa: E402

compile_cache.configure()
jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
jax.config.update('jax_persistent_cache_min_entry_size_bytes', 0)
