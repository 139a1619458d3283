"""Model FLOP/s utilisation: the forward and backward passes' operations a
sample (the benchmark's own count from shapes; K-FAC's work is not in it)
times the samples per second of the untraced whole periods, over the chips
times the chip's bf16 peak (``benchmark/peaks.json``, keyed by
``device_kind``; a device that is not there is an error)."""

import importlib

from benchmark import harness


def read(ctx):
    config = ctx.cell['config']
    flops = importlib.import_module(
        f"benchmark.flops.{config['kind']}"
    ).train_flops_per_sample(config)
    peaks = harness.load_json('peaks.json')
    kind = ctx.devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f'no peak for device kind {kind!r} in peaks.json')
    peak = peaks[kind]['bf16_flops_per_s'] * len(ctx.devices)
    return 100.0 * flops * ctx.throughput / peak
