"""What the program reports on itself, read for the per-layer metrics of
PR 25: the capture layer's device scopes, the host spans inside
``Trainer.step``, and the Newton-Schulz refresh counters in the engine's
state. The names are the benchmark's own copy, not imported from the
program: a program that lacks them (the parent commit) or renames them
gives nothing to read, and every reader then returns ``None``.

These readers pass their own names to ``trace_reduce``: the scopes that
the ``*.json`` rows name (``harness.trace_scopes``) stay as they are, so
the rows read what they read before.
"""

import statistics

from benchmark import trace_reduce

CAPTURE_A = 'kfac.capture_a'   # forward pass: A factors under the interceptor
CAPTURE_G = 'kfac.capture_g'   # backward pass: G factors under the g-taps
CAPTURE_PATCHES = CAPTURE_A + '/patches'  # a convolution's patch rows
PRE_STEP = 'kfac.host.pre_step'
LAUNCH = 'kfac.host.launch'
POST_STEP = 'kfac.host.post_step'
# the harness's spans around the program's: an idle gap goes to the
# innermost span that covers it
BENCH_SPANS = ('bench.input', 'bench.dispatch', 'bench.sync')


def capture_ms(ctx, scope):
    """Device milliseconds under ``scope`` per capturing step, on the
    device with most; an operation under both sides' paths would go to
    the deeper. ``CAPTURE_PATCHES`` is asked for alone: the patches are
    the A side's too."""
    steps = ctx.count('capture')
    if not steps:
        return None
    scopes = (scope,) if scope == CAPTURE_PATCHES else (CAPTURE_A, CAPTURE_G)
    worst = 0.0
    for plane in trace_reduce.device_planes(ctx.trace):
        under = trace_reduce.scope_ns(
            plane, ctx.windows[plane['name']], scopes
        )
        worst = max(worst, under.get(scope, 0.0))
    return worst / 1e6 / steps if worst else None


def host_ms(ctx, span):
    """Median milliseconds of the host span ``span`` over the traced
    steps."""
    found = trace_reduce.host_spans(ctx.trace, (span,))
    if not found:
        return None
    return statistics.median(e['duration_ns'] for e in found) / 1e6


def idle_ms(ctx, span):
    """Milliseconds a traced step in which the device ran nothing while
    the host was inside ``span`` (and inside no shorter span), on the
    device that idled there longest."""
    steps = ctx.count(None)
    if not steps or not trace_reduce.host_spans(ctx.trace, (span,)):
        return None
    spans = trace_reduce.host_spans(
        ctx.trace, BENCH_SPANS + (PRE_STEP, LAUNCH, POST_STEP)
    )
    worst = 0.0
    for plane in trace_reduce.device_planes(ctx.trace):
        gaps = dict(trace_reduce.idle_gaps(
            plane, ctx.windows[plane['name']], spans, n=len(spans) + 1
        ))
        worst = max(worst, gaps.get(span, 0.0))
    return 1e3 * worst / steps


def refresh_totals(ctx):
    """``DistributedKFAC.refresh_report``'s totals for the state the
    traced stretch left: its refresh step is the last refresh the state
    saw, a warm-started one. ``None`` where the engine has no report."""
    report = getattr(ctx.run.trainer.kfac, 'refresh_report', None)
    if report is None:
        return None
    return report(ctx.run.state.kfac_state).get('totals') or None
