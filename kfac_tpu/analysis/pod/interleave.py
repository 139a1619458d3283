"""Pod tier, stage 2: bounded model check of declared protocol tables.

``resilience/manager.py`` and ``resilience/chaos.py`` declare their
coordination protocols as module-level ``*_PROTOCOL`` dict literals —
a *sequence* machine for checkpoint save (ordered steps with ranks and
filesystem effects) and a *state* machine for a pod worker's life in a
storm (states, events, vote outcomes, what each transition mutates). This module
replays those tables against the invariants the fault injectors probe:

- **sequence machines**: single-writer discipline for the LATEST
  pointer, a barrier between the rank-0 stale-directory clear and the
  all-rank step write, commit only after the async write is awaited —
  each checked by replaying every crash prefix (the event alphabet the
  crash-point injector drives), so "a crash here leaves LATEST naming
  uncommitted bytes" is found by actually crashing there;
- **state machines**: reachability of every declared state, totality of
  the vote outcome wherever a vote can happen (both ``vote-commit`` and
  ``vote-abort`` must leave the voting state — a missing abort edge is
  a wedge under the signal injector), purity of the abort path (an
  abort that mutates is a half-applied migration), and a bounded
  exploration of event sequences — with a synthesized ``crash`` event
  resetting to the initial state at every point — asserting at most one
  mutating commit lands per checkpoint boundary.

Tables are literals checked without importing the declaring module, so
this is cheap enough for ``make lint``; the companion *code*
cross-check (the table's ``function`` must actually reach ops of the
declared kinds) lives in ``pod/rules.py`` on top of
``protocol.PodAnalysis`` reach queries, which is what keeps a table
honest when someone deletes the real barrier but not its row.
"""

from __future__ import annotations

#: exploration depth for state-machine event sequences; deep enough for
#: two full migrate cycles plus injected crashes, small enough for lint
MAX_TRACE_LEN = 8

_SEQ_KEYS = {'machine', 'name', 'function', 'steps'}
_STATE_KEYS = {'machine', 'name', 'function', 'vote_op', 'states',
               'initial', 'transitions'}


def check_table(table: dict) -> list[str]:
    """All invariant violations in one parsed ``*_PROTOCOL`` table."""
    machine = table.get('machine')
    if machine == 'sequence':
        return _check_sequence(table)
    if machine == 'state':
        return _check_state(table)
    return [
        "protocol table must declare machine: 'sequence' or 'state', "
        f'got {machine!r}'
    ]


# ----------------------------------------------------------------- sequence


def _check_sequence(table: dict) -> list[str]:
    problems = [
        f'sequence table is missing key {key!r}'
        for key in sorted(_SEQ_KEYS - set(table))
    ]
    steps = table.get('steps', ())
    if not isinstance(steps, (list, tuple)) or not steps or not all(
        isinstance(s, dict) and {'op', 'rank', 'kind'} <= set(s)
        for s in steps
    ):
        problems.append(
            'steps must be a non-empty sequence of dicts with op/rank/'
            'kind keys'
        )
        return problems

    for step in steps:
        kind, rank, op = step['kind'], step['rank'], step['op']
        if kind in ('barrier', 'collective', 'vote') and rank != 'all':
            problems.append(
                f'step {op!r}: a {kind} only rank {rank!r} enters '
                'deadlocks the ranks that do arrive'
            )
        if step.get('effect') == 'mutate_dir' and rank != 0:
            problems.append(
                f'step {op!r}: directory mutation must be single-writer '
                f'(rank 0), declared rank {rank!r} races concurrent '
                'writers'
            )
        if step.get('effect') == 'point_latest' and rank != 0:
            problems.append(
                f'step {op!r}: the LATEST pointer must have a single '
                f'writer (rank 0), declared rank {rank!r}'
            )
        if step.get('effect') == 'write_latest_inplace':
            problems.append(
                f'step {op!r}: in-place LATEST write can tear on crash; '
                'write a temp file and os.replace it (effect '
                'point_latest)'
            )

    problems.extend(_replay_crash_prefixes(steps))
    return problems


def _replay_crash_prefixes(steps) -> list[str]:
    """Replay every crash prefix of the step sequence and assert the
    LATEST pointer never names uncommitted bytes and the cleared stale
    dir is barrier-ordered before the all-rank rewrite."""
    problems: list[str] = []
    seen: set[str] = set()
    for crash_at in range(1, len(steps) + 1):
        waited = False
        wrote = False
        clear_pending: str | None = None
        commits = 0
        for step in steps[:crash_at]:
            kind, op = step['kind'], step['op']
            effect = step.get('effect')
            if kind == 'barrier':
                clear_pending = None
            elif kind == 'wait':
                waited = True
            if effect == 'mutate_dir':
                clear_pending = op
            elif effect == 'write_step_dir':
                if clear_pending is not None:
                    msg = (
                        f'no barrier between rank-0 {clear_pending!r} '
                        f'and all-rank {op!r}: a peer can write into '
                        'the directory rank 0 is still clearing'
                    )
                    if msg not in seen:
                        seen.add(msg)
                        problems.append(msg)
                wrote = True
                waited = False
            elif effect == 'point_latest':
                commits += 1
                if wrote and not waited:
                    msg = (
                        f'{op!r} commits LATEST before the async write '
                        'is awaited: a crash in the window leaves the '
                        'pointer naming uncommitted bytes (crash prefix '
                        f'of length {crash_at})'
                    )
                    if msg not in seen:
                        seen.add(msg)
                        problems.append(msg)
                if commits > 1:
                    msg = 'more than one LATEST commit in a single save'
                    if msg not in seen:
                        seen.add(msg)
                        problems.append(msg)
    return problems


# -------------------------------------------------------------------- state


def _check_state(table: dict) -> list[str]:
    problems = [
        f'state table is missing key {key!r}'
        for key in sorted(_STATE_KEYS - set(table))
    ]
    states = table.get('states', ())
    initial = table.get('initial')
    transitions = table.get('transitions', ())
    if not isinstance(transitions, (list, tuple)) or not all(
        isinstance(t, dict) and {'from', 'event', 'to', 'mutates'}
        <= set(t) for t in transitions
    ):
        problems.append(
            'transitions must be dicts with from/event/to/mutates keys'
        )
        return problems
    if initial not in states:
        problems.append(f'initial state {initial!r} is not in states')
        return problems

    out: dict[str, list[dict]] = {s: [] for s in states}
    for t in transitions:
        for end in ('from', 'to'):
            if t[end] not in states:
                problems.append(
                    f'transition {t["event"]!r} references undeclared '
                    f'state {t[end]!r}'
                )
        if t['from'] in out:
            out[t['from']].append(t)

    if problems:
        return problems

    # reachability: every declared state must be exercisable, else the
    # fault injectors can never drive the machine there
    seen = {initial}
    frontier = [initial]
    while frontier:
        for t in out[frontier.pop()]:
            if t['to'] not in seen:
                seen.add(t['to'])
                frontier.append(t['to'])
    for state in states:
        if state not in seen:
            problems.append(
                f'state {state!r} is unreachable from {initial!r}'
            )

    # vote totality and abort purity
    for state in states:
        events = {t['event'] for t in out[state]}
        has_commit = 'vote-commit' in events
        has_abort = 'vote-abort' in events
        if has_commit != has_abort:
            missing = 'vote-abort' if has_commit else 'vote-commit'
            problems.append(
                f'state {state!r} handles one vote outcome but not '
                f'{missing!r}: a losing vote wedges the fleet there'
            )
    for t in transitions:
        mutates = tuple(t.get('mutates') or ())
        if mutates and t['event'] != 'vote-commit':
            problems.append(
                f'transition {t["event"]!r} mutates {mutates!r} without '
                'a committed vote: peers that voted differently apply '
                'different state'
            )

    problems.extend(_explore_state_machine(out, initial))
    return problems


def _explore_state_machine(out, initial) -> list[str]:
    """Bounded exploration over the event alphabet plus a synthesized
    ``crash`` event (restart to initial) at every point: at most one
    mutating transition may land between checkpoint boundaries."""
    problems: list[str] = []
    # (state, mutations since last boundary) — the abstraction is exact
    # for the per-boundary commit-count invariant
    start = (initial, 0)
    visited = {start}
    frontier = [start]
    depth = 0
    while frontier and depth < MAX_TRACE_LEN:
        depth += 1
        nxt = []
        for state, commits in frontier:
            successors = [
                (
                    t['to'],
                    0 if t['event'] == 'checkpoint-boundary'
                    else commits + (1 if tuple(t.get('mutates') or ())
                                    else 0),
                )
                for t in out[state]
            ]
            successors.append((initial, commits))  # crash + restart
            for succ in successors:
                if succ[1] > 1:
                    problems.append(
                        'a reachable event sequence lands more than one '
                        'mutating commit between checkpoint boundaries '
                        f'(via state {state!r})'
                    )
                    return problems
                if succ not in visited:
                    visited.add(succ)
                    nxt.append(succ)
        frontier = nxt
    return problems
