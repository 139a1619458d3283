"""Look at a profiler trace by hand, and cut a small piece of it for the
reducer's tests.

    python3 benchmark/trace_look.py <dir or .xplane.pb> [--cut out.json]

Prints every plane and line with its event count, and for each line a few
events with all their statistics: which planes are devices, which are host
threads, how kernels and scopes are named. ``--cut`` writes the neutral
trace (``benchmark/trace_reduce.py``) of a short slice around the first
device operation: the lines the reducer reads, nothing else.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import trace_reduce  # noqa: E402


def look(path: str, samples: int) -> None:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        print(f'plane {plane.name!r}')
        for line in plane.lines:
            events = list(line.events)
            total = sum(e.duration_ns for e in events)
            print(f'  line {line.name!r}: {len(events)} events, '
                  f'{total / 1e6:.3f} ms summed')
            names = collections.Counter(e.name for e in events)
            print(f'    most frequent: {names.most_common(8)}')
            by_time = collections.Counter()
            for e in events:
                by_time[e.name] += e.duration_ns
            print('    most time: '
                  f'{[(n, round(t / 1e6, 3)) for n, t in by_time.most_common(8)]}')
            for e in events[:samples]:
                stats = {k: (v if not isinstance(v, str) else v[:300])
                         for k, v in e.stats}
                print(f'    {e.name!r} start {e.start_ns:.0f} dur '
                      f'{e.duration_ns:.0f} stats {stats}')


def cut(path: str, out: str, slice_ns: float) -> None:
    trace = trace_reduce.from_xplane(path, trace_reduce.wanted_line)
    planes = trace_reduce.device_planes(trace)
    if not planes or not trace_reduce.ops(planes[0]):
        raise SystemExit('no device operation in the trace')
    # from just before the first device operation, so that the slice has
    # the end of a host span, an idle gap and the first operations
    lo = trace_reduce.ops(planes[0])[0]['start_ns'] - slice_ns / 4
    hi = lo + slice_ns
    for plane in trace['planes']:
        for line in plane['lines']:
            line['events'] = [
                e for e in line['events']
                if e['start_ns'] < hi and e['start_ns'] + e['duration_ns'] > lo
                and (plane['name'] != trace_reduce.HOST_PLANE
                     or e['name'].startswith(('bench.', 'train')))
            ]
        plane['lines'] = [l for l in plane['lines'] if l['events']]
    trace['planes'] = [p for p in trace['planes'] if p['lines']]
    with open(out, 'w') as f:
        json.dump(trace, f)
    n = sum(len(l['events']) for p in trace['planes'] for l in p['lines'])
    print(f'wrote {out}: {n} events in [{lo:.0f}, {hi:.0f}) ns')


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument('path')
    p.add_argument('--samples', type=int, default=3)
    p.add_argument('--cut')
    p.add_argument('--slice-ms', type=float, default=3.0)
    args = p.parse_args()
    path = args.path
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    look(path, args.samples)
    if args.cut:
        cut(path, args.cut, args.slice_ms * 1e6)


if __name__ == '__main__':
    main()
