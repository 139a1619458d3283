"""kfaclint pod tier suite: KFL301–KFL305 fixtures, the happens-before
proof that retired KFL002's inline suppressions, protocol-table model
checking, suppression/baseline round-trips, and the head-clean gate.

Convention matches tests/test_kfaclint.py: every rule is demonstrated
by a true-positive fixture asserted to flag *under that rule* and to be
clean under every other pod rule, so unregistering a rule fails its
fixture test.
"""

import os
import textwrap

import pytest

from kfac_tpu import analysis
from kfac_tpu.analysis import core
from kfac_tpu.analysis.pod import interleave, protocol

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_snippet(tmp_path, source, codes=None, filename='mod.py'):
    path = tmp_path / filename
    path.write_text(textwrap.dedent(source))
    project, errors = analysis.load_project(str(tmp_path))
    rules = analysis.get_rules(codes or analysis.POD_RULE_CODES)
    return analysis.analyze(project, rules, parse_errors=errors)


def codes_of(findings):
    return sorted({f.code for f in findings})


OTHER = {
    code: [c for c in analysis.POD_RULE_CODES if c != code]
    for code in analysis.POD_RULE_CODES
}


# ------------------------------------------------------------------ KFL301


KFL301_TP = '''
    from kfac_tpu.parallel import multihost

    def sync(x):
        if multihost.process_index() == 0:
            multihost.barrier('a')
            vals = multihost.allgather_scalars(x)
        else:
            vals = multihost.allgather_scalars(x)
            multihost.barrier('a')
        return vals
'''


def test_kfl301_flags_reordered_collectives(tmp_path):
    findings = run_snippet(tmp_path, KFL301_TP, ['KFL301'])
    assert len(findings) == 1
    assert 'different order' in findings[0].message


def test_kfl301_silent_when_disabled(tmp_path):
    assert run_snippet(tmp_path, KFL301_TP, OTHER['KFL301']) == []


def test_kfl301_clean_when_arms_agree(tmp_path):
    # identical blocking sequences on both arms pair rank-for-rank
    assert run_snippet(tmp_path, '''
        from kfac_tpu.parallel import multihost

        def sync(x):
            if multihost.process_index() == 0:
                multihost.barrier('a')
                vals = multihost.allgather_scalars(x)
            else:
                multihost.barrier('a')
                vals = multihost.allgather_scalars(x)
            return vals
    ''') == []


# ------------------------------------------------------------------ KFL302


KFL302_TP = '''
    from kfac_tpu.parallel import multihost

    def migrate(ok):
        if multihost.process_index() == 0:
            ok = multihost.agree_decision(ok)
        return ok
'''


def test_kfl302_flags_rank0_only_vote(tmp_path):
    findings = run_snippet(tmp_path, KFL302_TP, ['KFL302'])
    assert len(findings) == 1
    assert 'agree_decision' in findings[0].message


def test_kfl302_silent_when_disabled(tmp_path):
    assert run_snippet(tmp_path, KFL302_TP, OTHER['KFL302']) == []


def test_kfl302_flags_collective_after_rank_return(tmp_path):
    findings = run_snippet(tmp_path, '''
        from kfac_tpu.parallel import multihost

        def commit(path):
            if multihost.process_index() != 0:
                return
            multihost.barrier('commit')
    ''', ['KFL302'])
    assert len(findings) == 1
    assert 'early rank-guard return' in findings[0].message


def test_kfl302_flags_rank_dependent_loop(tmp_path):
    findings = run_snippet(tmp_path, '''
        from kfac_tpu.parallel import multihost

        def drain(items):
            pidx = multihost.process_index()
            for _ in range(pidx):
                multihost.barrier('drain')
    ''', ['KFL302'])
    assert len(findings) == 1
    assert 'trip count' in findings[0].message


def test_kfl302_flags_opaque_rank_branch(tmp_path):
    # the rank test flows through a local: still divergent
    findings = run_snippet(tmp_path, '''
        from kfac_tpu.parallel import multihost

        def maybe(x):
            is_writer = multihost.process_index() == 0
            extra = compute(x)
            if is_writer and extra:
                multihost.barrier('w')
    ''', ['KFL302'])
    assert len(findings) == 1


def test_kfl302_clean_on_uniform_guards(tmp_path):
    # count guards and plain config guards are uniform across ranks —
    # the multihost module's own single-host fast paths must not flag
    assert run_snippet(tmp_path, '''
        import jax
        from kfac_tpu.parallel import multihost

        def barrier_like(name, every, step):
            if jax.process_count() == 1:
                return
            if step % every != 0:
                return
            multihost.barrier(name)
    ''') == []


def test_kfl302_clean_on_inexact_single_writer(tmp_path):
    # `rank test AND unknown` bounds who may enter but proves nothing;
    # blocking ops are not inside the branch, so no finding (the flight
    # recorder's rank-0 bundle shape)
    assert run_snippet(tmp_path, '''
        import os
        from kfac_tpu.parallel import multihost

        def observe(out):
            if multihost.process_index() != 0:
                return None
            return write_bundle(out)

        def write_bundle(out):
            os.makedirs(out, exist_ok=True)
            return out
    ''', ['KFL301', 'KFL302', 'KFL303']) == []


# ------------------------------------------------------------------ KFL303


KFL303_TP = '''
    import jax

    @jax.jit
    def step(x):
        return x * 2

    def drive(x):
        pidx = jax.process_index()
        return step(x[: pidx + 1])
'''


def test_kfl303_flags_rank_tainted_operand(tmp_path):
    findings = run_snippet(tmp_path, KFL303_TP, ['KFL303'])
    assert len(findings) == 1
    assert 'process_index()-derived operand' in findings[0].message


def test_kfl303_silent_when_disabled(tmp_path):
    assert run_snippet(tmp_path, KFL303_TP, OTHER['KFL303']) == []


def test_kfl303_flags_divergent_launch(tmp_path):
    findings = run_snippet(tmp_path, '''
        import jax

        @jax.jit
        def step(x):
            return x * 2

        def drive(x):
            if jax.process_index() == 0:
                return step(x)
            return x
    ''', ['KFL303'])
    assert len(findings) == 1
    assert 'rank-divergent branch' in findings[0].message


def test_kfl303_clean_on_uniform_launch(tmp_path):
    assert run_snippet(tmp_path, '''
        import jax

        @jax.jit
        def step(x):
            return x * 2

        def drive(x):
            return step(x)
    ''') == []


# ------------------------------------------------------------------ KFL304


# the CheckpointManager.save shape with its barrier doctored out: the
# rank-0 stale-dir clear hides inside a retry lambda — this is the
# committed true-positive that stands in for the retired inline KFL002
# suppressions (acceptance bar: deleting the barrier must flag)
KFL304_TP = '''
    import os
    import shutil
    from kfac_tpu.parallel import multihost

    def _with_retries(what, fn):
        return fn()

    def save(state, sdir):
        if multihost.process_index() == 0 and os.path.exists(sdir):
            _with_retries('clearing stale dir',
                          lambda: shutil.rmtree(sdir))
        write(state, sdir)
'''


def test_kfl304_flags_unordered_lambda_mutation(tmp_path):
    findings = run_snippet(tmp_path, KFL304_TP, ['KFL304'])
    assert len(findings) == 1
    assert 'shutil.rmtree()' in findings[0].message
    assert 'rank 0 only' in findings[0].message


def test_kfl304_silent_when_disabled(tmp_path):
    assert run_snippet(tmp_path, KFL304_TP, OTHER['KFL304']) == []


def test_kfl304_cleared_by_barrier_in_same_function(tmp_path):
    src = KFL304_TP.replace(
        'write(state, sdir)',
        "multihost.barrier('save')\n        write(state, sdir)",
    )
    assert run_snippet(tmp_path, src, ['KFL304']) == []


def test_kfl304_cleared_by_ordering_in_calling_context(tmp_path):
    # the happens-before proof is cross-function: a wait op in the only
    # calling context orders the callee's rank-0 mutation
    assert run_snippet(tmp_path, '''
        import os

        def _commit(path):
            import jax
            if jax.process_index() != 0:
                return
            os.replace(path + '.tmp', path)

        def finish(ckptr, path):
            ckptr.wait_until_finished()
            _commit(path)
    ''', ['KFL304']) == []


def test_kfl304_one_unordered_root_defeats_the_proof(tmp_path):
    # same callee, two roots: one ordered, one not -> still a race
    findings = run_snippet(tmp_path, '''
        import os

        def _commit(path):
            import jax
            if jax.process_index() != 0:
                return
            os.replace(path + '.tmp', path)

        def finish(ckptr, path):
            ckptr.wait_until_finished()
            _commit(path)

        def hotpath(path):
            _commit(path)
    ''', ['KFL304'])
    assert len(findings) == 1
    assert 'hotpath' in findings[0].message


def test_kfl002_drops_findings_the_pod_proof_clears(tmp_path):
    # KFL002 alone cannot see the caller's ordering op; with the pod
    # proof consulted it stays silent — the mechanism that retired the
    # four inline suppressions in checkpoint.py / resilience/manager.py
    src = '''
        import os

        def _commit(path):
            import jax
            if jax.process_index() != 0:
                return
            os.replace(path + '.tmp', path)

        def finish(ckptr, path):
            ckptr.wait_until_finished()
            _commit(path)
    '''
    assert run_snippet(tmp_path, src, ['KFL002']) == []
    # ...and removing the ordering edge brings KFL002 back
    doctored = src.replace('ckptr.wait_until_finished()', 'pass')
    findings = run_snippet(tmp_path, doctored, ['KFL002'])
    assert codes_of(findings) == ['KFL002']


def test_retired_suppressions_are_gone():
    # the four inline KFL002 suppressions are retired for good; the
    # doctored fixture above is the surviving true-positive record
    for rel in ('kfac_tpu/checkpoint.py', 'kfac_tpu/resilience/manager.py'):
        with open(os.path.join(REPO_ROOT, rel), encoding='utf-8') as f:
            assert 'disable=KFL002' not in f.read(), rel


# ------------------------------------------------------------------ KFL305


KFL305_TP = '''
    SAVE_PROTOCOL = {
        'machine': 'sequence',
        'name': 'save',
        'function': 'save',
        'steps': (
            {'op': 'clear', 'rank': 0, 'kind': 'mutate',
             'effect': 'mutate_dir'},
            {'op': 'write', 'rank': 'all', 'kind': 'mutate',
             'effect': 'write_step_dir'},
            {'op': 'commit', 'rank': 0, 'kind': 'mutate',
             'effect': 'point_latest'},
        ),
    }

    def save():
        pass
'''


def test_kfl305_flags_doctored_save_sequence(tmp_path):
    findings = run_snippet(tmp_path, KFL305_TP, ['KFL305'])
    msgs = [f.message for f in findings]
    assert any('no barrier between' in m for m in msgs), msgs
    assert any('before the async write is awaited' in m for m in msgs)


def test_kfl305_silent_when_disabled(tmp_path):
    assert run_snippet(tmp_path, KFL305_TP, OTHER['KFL305']) == []


def test_kfl305_flags_code_drift_from_table(tmp_path):
    # a well-formed table whose function no longer takes the declared
    # barrier/wait ops: the cross-check rots with the code
    findings = run_snippet(tmp_path, '''
        SAVE_PROTOCOL = {
            'machine': 'sequence',
            'name': 'save',
            'function': 'save',
            'steps': (
                {'op': 'barrier', 'rank': 'all', 'kind': 'barrier'},
                {'op': 'write', 'rank': 'all', 'kind': 'mutate',
                 'effect': 'write_step_dir'},
                {'op': 'wait', 'rank': 'all', 'kind': 'wait'},
                {'op': 'commit', 'rank': 0, 'kind': 'mutate',
                 'effect': 'point_latest'},
            ),
        }

        def save(state):
            return state
    ''', ['KFL305'])
    msgs = [f.message for f in findings]
    assert any('barrier' in m and 'no longer reaches' in m for m in msgs)
    assert any('wait' in m and 'no longer reaches' in m for m in msgs)


def test_kfl305_flags_missing_vote_outcome(tmp_path):
    findings = run_snippet(tmp_path, '''
        from kfac_tpu.parallel import multihost

        MIGRATE_PROTOCOL = {
            'machine': 'state',
            'name': 'migrate',
            'function': 'migrate',
            'vote_op': 'agree_decision',
            'states': ('idle', 'boundary', 'committed'),
            'initial': 'idle',
            'transitions': (
                {'from': 'idle', 'event': 'checkpoint-boundary',
                 'to': 'boundary', 'mutates': ()},
                {'from': 'boundary', 'event': 'vote-commit',
                 'to': 'committed', 'mutates': ('plan',)},
                {'from': 'committed', 'event': 'cooldown',
                 'to': 'idle', 'mutates': ()},
            ),
        }

        def migrate(ok):
            return multihost.agree_decision(ok)
    ''', ['KFL305'])
    assert any('vote-abort' in f.message for f in findings), findings


def test_kfl305_flags_mutating_abort(tmp_path):
    findings = run_snippet(tmp_path, '''
        from kfac_tpu.parallel import multihost

        MIGRATE_PROTOCOL = {
            'machine': 'state',
            'name': 'migrate',
            'function': 'migrate',
            'vote_op': 'agree_decision',
            'states': ('boundary', 'committed', 'aborted'),
            'initial': 'boundary',
            'transitions': (
                {'from': 'boundary', 'event': 'vote-commit',
                 'to': 'committed', 'mutates': ('plan',)},
                {'from': 'boundary', 'event': 'vote-abort',
                 'to': 'aborted', 'mutates': ('plan',)},
            ),
        }

        def migrate(ok):
            return multihost.agree_decision(ok)
    ''', ['KFL305'])
    assert any(
        'without a committed vote' in f.message for f in findings
    ), findings


def test_kfl305_flags_lost_vote_op(tmp_path):
    findings = run_snippet(tmp_path, '''
        MIGRATE_PROTOCOL = {
            'machine': 'state',
            'name': 'migrate',
            'function': 'migrate',
            'vote_op': 'agree_decision',
            'states': ('boundary', 'committed', 'aborted'),
            'initial': 'boundary',
            'transitions': (
                {'from': 'boundary', 'event': 'vote-commit',
                 'to': 'committed', 'mutates': ('plan',)},
                {'from': 'boundary', 'event': 'vote-abort',
                 'to': 'aborted', 'mutates': ()},
            ),
        }

        def migrate(ok):
            return ok
    ''', ['KFL305'])
    assert any(
        'no longer reaches it' in f.message for f in findings
    ), findings


def test_kfl305_clean_on_sound_tables(tmp_path):
    assert run_snippet(tmp_path, '''
        from kfac_tpu.parallel import multihost

        SAVE_PROTOCOL = {
            'machine': 'sequence',
            'name': 'save',
            'function': 'save',
            'steps': (
                {'op': 'clear', 'rank': 0, 'kind': 'mutate',
                 'effect': 'mutate_dir'},
                {'op': 'barrier', 'rank': 'all', 'kind': 'barrier'},
                {'op': 'write', 'rank': 'all', 'kind': 'mutate',
                 'effect': 'write_step_dir'},
                {'op': 'wait', 'rank': 'all', 'kind': 'wait'},
                {'op': 'commit', 'rank': 0, 'kind': 'mutate',
                 'effect': 'point_latest'},
            ),
        }

        def save(ckptr):
            multihost.barrier('save')
            ckptr.wait_until_finished()
    ''') == []


# ----------------------------------------------------- interleave unit checks


def test_interleave_rejects_unknown_machine():
    assert interleave.check_table({'machine': 'petri-net'})


def test_interleave_rejects_non_all_barrier():
    problems = interleave.check_table({
        'machine': 'sequence', 'name': 'x', 'function': 'f',
        'steps': ({'op': 'b', 'rank': 0, 'kind': 'barrier'},),
    })
    assert any('deadlocks' in p for p in problems)


def test_interleave_flags_unreachable_state():
    problems = interleave.check_table({
        'machine': 'state', 'name': 'x', 'function': 'f',
        'vote_op': 'agree_decision',
        'states': ('a', 'b', 'orphan'), 'initial': 'a',
        'transitions': (
            {'from': 'a', 'event': 'go', 'to': 'b', 'mutates': ()},
        ),
    })
    assert any('unreachable' in p for p in problems)


def test_interleave_flags_double_commit_per_boundary():
    # two mutating commits reachable without a checkpoint boundary
    # between them — found by the bounded exploration, not structurally
    problems = interleave.check_table({
        'machine': 'state', 'name': 'x', 'function': 'f',
        'vote_op': 'agree_decision',
        'states': ('boundary', 'committed'), 'initial': 'boundary',
        'transitions': (
            {'from': 'boundary', 'event': 'vote-commit',
             'to': 'committed', 'mutates': ('plan',)},
            {'from': 'boundary', 'event': 'vote-abort',
             'to': 'boundary', 'mutates': ()},
            {'from': 'committed', 'event': 'vote-commit',
             'to': 'committed', 'mutates': ('plan',)},
            {'from': 'committed', 'event': 'vote-abort',
             'to': 'boundary', 'mutates': ()},
        ),
    })
    assert any('more than one mutating commit' in p for p in problems)


# ----------------------------------------------------- suppression / baseline


def test_pod_findings_honor_suppressions(tmp_path):
    src = KFL302_TP.replace(
        'ok = multihost.agree_decision(ok)',
        'ok = multihost.agree_decision(ok)  '
        '# kfaclint: disable=KFL302 (fixture: single-host test shim)',
    )
    assert run_snippet(tmp_path, src, ['KFL302']) == []
    # reason-less suppression does not suppress and is itself KFL000
    bare = KFL302_TP.replace(
        'ok = multihost.agree_decision(ok)',
        'ok = multihost.agree_decision(ok)  # kfaclint: disable=KFL302',
    )
    findings = run_snippet(tmp_path, bare, ['KFL302'])
    assert 'KFL000' in codes_of(findings)


def test_pod_findings_baseline_round_trip(tmp_path):
    findings = run_snippet(tmp_path, KFL304_TP, ['KFL304'])
    assert findings
    bpath = tmp_path / 'baseline.json'
    analysis.save_baseline(str(bpath), findings)
    new, matched = analysis.split_baseline(
        findings, analysis.load_baseline(str(bpath))
    )
    assert not new and matched == len(findings)


# ------------------------------------------------------------- head cleanness


def test_pod_rules_clean_on_head():
    """KFL301–KFL305 and KFL002 hold on the repo itself with an empty
    baseline — including the four KFL002 sites whose suppressions the
    pod proof retired."""
    project, errors = analysis.load_project(REPO_ROOT, ['kfac_tpu'])
    rules = analysis.get_rules(
        tuple(analysis.POD_RULE_CODES) + ('KFL002',)
    )
    findings = analysis.analyze(project, rules, parse_errors=errors)
    assert findings == [], [f.render() for f in findings]


def test_head_declares_the_save_protocol_table():
    project, _ = analysis.load_project(REPO_ROOT, ['kfac_tpu'])
    tables, problems = protocol.load_protocol_tables(project)
    assert problems == []
    names = {t.name for t in tables}
    assert 'SAVE_PROTOCOL' in names
    machines = {t.table['machine'] for t in tables}
    assert machines == {'sequence', 'state'}


def test_registry_parses_from_multihost_ast():
    project, _ = analysis.load_project(REPO_ROOT, ['kfac_tpu'])
    registry = protocol.load_op_registry(project)
    assert registry == protocol.DEFAULT_PROTOCOL_OPS, (
        'PROTOCOL_OPS in kfac_tpu/parallel/multihost.py must stay in '
        'sync with the pod tier fallback copy'
    )
