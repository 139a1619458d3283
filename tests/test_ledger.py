"""Unified run ledger tests (docs/OBSERVABILITY.md "Run ledger").

Pins PR 18's acceptance criteria:

- every stream adapter parses its committed format from the
  ``tests/data/mini_ledger/`` fixture (counts, kinds, run-header
  ``run_id``; header-less files stay valid with ``run_id=None``);
- the correlated timeline over the fixture is byte-identical to
  ``TIMELINE.golden`` through both CLIs (``kfac_ledger --timeline``
  and ``kfac_inspect --timeline``) and joins >= 3 streams;
- each correlation rule has a true positive AND a clean negative
  (missing chain link, out-of-join-window, non-reaction fleet event);
- the perf-regression sentinel passes a clean same-provenance round,
  fails a doctored 1.5x regression with the named key and exit code 1,
  and REFUSES a cross-provenance comparison with exit code 2;
- the committed baseline artifact is deterministic (byte-identical
  rebuilds) and schema-checked on load;
- the shared run-header rides ``JSONLWriter`` (stamped once per file,
  re-stamped after rotation, never duplicated on append),
  ``PostmortemWriter`` MANIFESTs, and the Trainer -> compile-watch
  thread;
- KFL113 pins the doc tables to the live registries.

Compile budget: everything here is host-side parsing — the one Trainer
test only constructs (never steps) the engine, so the module adds zero
XLA compiles.
"""

import json
import os
import subprocess
import sys

import pytest

from kfac_tpu.analysis import drift
from kfac_tpu.observability import ledger
from kfac_tpu.observability.flight_recorder import PostmortemWriter
from kfac_tpu.observability.sinks import JSONLWriter

REPO = os.path.join(os.path.dirname(__file__), os.pardir)
FIXTURE = os.path.join(os.path.dirname(__file__), 'data', 'mini_ledger')
LEDGER_CLI = os.path.join(REPO, 'tools', 'kfac_ledger.py')
INSPECT_CLI = os.path.join(REPO, 'tools', 'kfac_inspect.py')


def _fixture(name):
    return os.path.join(FIXTURE, name)


def _golden():
    with open(_fixture('TIMELINE.golden'), encoding='utf-8') as f:
        return f.read()


def _fixture_ledger():
    rl = ledger.RunLedger()
    rl.ingest_dir(FIXTURE)
    return rl


def _cli(*args):
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        cwd=REPO, timeout=120)


# ---------------------------------------------------------------- adapters


@pytest.mark.parametrize('stream, fname, count, kinds', [
    ('metrics', 'metrics.jsonl', 12, {'record'}),
    ('flight', 'flight.jsonl', 3, {'record'}),
    ('compile', 'compile.jsonl', 6, {'compile_phase'}),
    ('calibration', 'calib.jsonl', 3, {'record'}),
    ('fleet', 'fleet.jsonl', 4, {'fleet_event'}),
    ('chaos', 'chaos.jsonl', 7, {'chaos_event'}),
    ('trace', 'trace.json', 3, {'trace_step', 'trace_summary'}),
    ('serving', 'serving.jsonl', 4, {'serve'}),
    ('bench', 'bench_round.json', 1, {'bench_round'}),
])
def test_adapter_parses_committed_format(stream, fname, count, kinds):
    events = ledger.ADAPTERS[stream](_fixture(fname))
    assert len(events) == count
    assert {e['stream'] for e in events} == {stream}
    assert {e['kind'] for e in events} == kinds
    # the shared run-header names the run on every event
    assert {e['run_id'] for e in events} == {'mini0001'}
    # normalized schema: every adapter emits exactly these keys
    for e in events:
        assert set(e) == {
            'run_id', 'stream', 'step', 't', 'kind', 'detail', 'data'}


def test_headerless_sources_stay_valid(tmp_path):
    # iterable of raw records: no header, run_id stays None
    events = ledger.parse_metrics([{'step': 0, 'loss': 1.0}])
    assert [e['run_id'] for e in events] == [None]
    # same for an on-disk header-less JSONL (the pre-PR-18 format)
    p = tmp_path / 'metrics.jsonl'
    p.write_text(json.dumps({'step': 3, 'loss': 0.5}) + '\n')
    events = ledger.parse_metrics(p)
    assert len(events) == 1
    assert events[0]['run_id'] is None
    assert events[0]['step'] == 3


def test_run_header_shape_and_consumption():
    hdr = ledger.run_header('abc123', 'metrics')
    assert hdr == {'kind': 'run_header', 'run_id': 'abc123',
                   'schema': ledger.LEDGER_SCHEMA, 'stream': 'metrics'}
    # the header is consumed, not emitted as an event
    events = ledger.parse_metrics([hdr, {'step': 0, 'loss': 1.0}])
    assert len(events) == 1
    assert events[0]['run_id'] == 'abc123'


def test_new_run_id_format():
    rid = ledger.new_run_id()
    assert len(rid) == 12 and rid == rid.lower()
    int(rid, 16)  # hex
    assert ledger.new_run_id() != rid


def test_ingest_dir_discovers_every_stream():
    rl = _fixture_ledger()
    assert rl.runs() == ['mini0001']
    assert rl.streams() == sorted(ledger.ADAPTERS)
    assert len(rl.events) == 43


def test_step_clock_places_wall_clock_only_events():
    """The compile journal carries only wall clock; the chaos worker's
    (step, t) anchors teach the ledger the run's step clock, which
    lands the n=2 recompile at step 5 — flagged as estimated."""
    rl = _fixture_ledger()
    done = [e for e in rl.events
            if e['stream'] == 'compile' and e['data'].get('n') == 2
            and e['data'].get('phase') == 'done']
    assert len(done) == 1
    assert done[0]['step'] == 5
    assert done[0]['data']['step_est'] is True


# ------------------------------------------------------------ correlations


def test_fixture_timeline_fires_expected_rules_only():
    rl = _fixture_ledger()
    fired = {c['rule'] for c in rl.correlations()}
    assert fired == {'recompile_cascade', 'recompile_step_spike',
                     'calib_fleet_reaction', 'preempt_recovery'}
    # clean negative: no divergence evidence in the fixture
    assert 'factor_divergence' not in fired


def test_recompile_cascade_joins_at_least_three_streams():
    rl = _fixture_ledger()
    cascade = [c for c in rl.correlations()
               if c['rule'] == 'recompile_cascade']
    assert len(cascade) == 1
    assert len(cascade[0]['streams']) >= 3
    assert {'compile', 'calibration', 'fleet'} <= set(cascade[0]['streams'])


def test_fleet_cooldown_is_not_a_reaction():
    """The fixture's step-10 ``cooldown`` event is a built-in negative:
    only the reaction events (drift/retune/armed/migrated) anomalize."""
    rl = _fixture_ledger()
    assert not any('cooldown' in a['detail'] for a in rl.anomalies())
    reactions = [a for a in rl.anomalies() if a['kind'] == 'fleet_reaction']
    assert len(reactions) == 3


def test_factor_divergence_positive_and_join_window_negative():
    cfg = ledger.LedgerConfig()
    hot = [{'step': 1, 'loss': 1.0, 'kfac/factor_norm': 1e9},
           {'step': 2, 'loss': float('nan')}]
    anomalies = ledger.derive_anomalies(ledger.parse_metrics(hot), cfg)
    assert sorted(a['kind'] for a in anomalies) == [
        'huge_factor', 'nonfinite_loss']
    assert {c['rule'] for c in ledger.correlate(anomalies, cfg)} == {
        'factor_divergence'}
    # same evidence outside join_steps: full-chain-or-nothing
    far = [{'step': 1, 'loss': 1.0, 'kfac/factor_norm': 1e9},
           {'step': 20, 'loss': float('nan')}]
    anomalies = ledger.derive_anomalies(ledger.parse_metrics(far), cfg)
    assert ledger.correlate(anomalies, cfg) == []


def test_step_spike_without_recompile_is_clean_negative():
    cfg = ledger.LedgerConfig()
    recs = [{'step': s, 'step_time_s': 0.1} for s in range(6)]
    recs.append({'step': 6, 'step_time_s': 0.25})
    anomalies = ledger.derive_anomalies(ledger.parse_metrics(recs), cfg)
    assert [a['kind'] for a in anomalies] == ['step_time_spike']
    assert ledger.correlate(anomalies, cfg) == []


# ----------------------------------------------------------- timeline CLIs


def test_timeline_byte_stable_against_golden():
    """Acceptance: the committed fixture renders a deterministic
    timeline, pinned byte-for-byte."""
    assert ledger.render_timeline(_fixture_ledger()) == _golden()
    # twice in-process: no hidden ordering nondeterminism
    assert ledger.render_timeline(_fixture_ledger()) == _golden()


def test_kfac_ledger_cli_timeline_matches_golden():
    out = _cli(LEDGER_CLI, '--timeline', FIXTURE)
    assert out.returncode == 0, out.stderr
    assert out.stdout == _golden()


def test_kfac_inspect_cli_timeline_matches_golden():
    """Satellite: the SAME report through the triage CLI — divergence
    and compile verdicts ride the timeline, not a separate tool."""
    out = _cli(INSPECT_CLI, '--timeline', FIXTURE)
    assert out.returncode == 0, out.stderr
    assert out.stdout == _golden()
    assert 'verdicts:' in out.stdout and 'compile:' in out.stdout


def test_timeline_report_json_shape():
    report = ledger.timeline_report(_fixture_ledger())
    assert report['schema'] == ledger.LEDGER_SCHEMA
    assert report['runs'] == ['mini0001']
    assert report['n_events'] == 43
    assert report['verdicts']['compile'].startswith('ok')
    assert report['verdicts']['divergence'].startswith('none')


# ---------------------------------------------------------------- sentinel


def _fixture_round():
    with open(_fixture('bench_round.json'), encoding='utf-8') as f:
        return json.load(f)


def _fixture_baseline():
    return ledger.load_baseline(_fixture('LEDGER.json'))


def test_sentinel_clean_round_passes():
    verdict = ledger.sentinel_check(_fixture_round(), _fixture_baseline())
    assert verdict['status'] == 'ok'
    assert verdict['regressed_keys'] == []
    assert all(v['verdict'] == 'ok' for v in verdict['keys'].values())


def test_sentinel_doctored_regression_names_the_key():
    """Acceptance: a doctored 1.5x throughput regression fails with the
    named key."""
    rnd = _fixture_round()
    rnd['parsed']['value'] /= 1.5
    verdict = ledger.sentinel_check(rnd, _fixture_baseline())
    assert verdict['status'] == 'regressed'
    assert verdict['regressed_keys'] == ['value']
    assert verdict['keys']['value']['verdict'] == 'regressed'
    # the other keys stay individually ok — one regression, one name
    assert verdict['keys']['sgd_tokens_per_sec']['verdict'] == 'ok'


def test_sentinel_refuses_cross_provenance():
    """Acceptance: a CPU-fallback round is never compared against TPU
    medians (the PR-11 replay-defense lesson)."""
    rnd = _fixture_round()
    rnd['parsed']['platform'] = 'cpu'
    verdict = ledger.sentinel_check(rnd, _fixture_baseline())
    assert verdict['status'] == 'refused'
    assert verdict['keys'] == {} and verdict['regressed_keys'] == []
    assert 'not compared' in verdict['reason']


def test_sentinel_missing_baseline_is_not_a_failure():
    verdict = ledger.sentinel_check(_fixture_round(), None)
    assert verdict['status'] == 'no_baseline'
    assert verdict['regressed_keys'] == []


def test_sentinel_lower_is_better_direction():
    rnd = _fixture_round()
    rnd['parsed']['acc_time_ratio'] *= 2.0  # overhead doubled
    verdict = ledger.sentinel_check(rnd, _fixture_baseline())
    assert verdict['status'] == 'regressed'
    assert verdict['regressed_keys'] == ['acc_time_ratio']


def test_cli_check_exit_codes(tmp_path):
    """Acceptance: exit 0 clean, 1 regressed (named key on stdout),
    2 refused."""
    base = _fixture('LEDGER.json')
    ok = _cli(LEDGER_CLI, '--check', _fixture('bench_round.json'),
              '--baseline', base)
    assert ok.returncode == 0, ok.stderr

    doctored = _fixture_round()
    doctored['parsed']['value'] /= 1.5
    bad = tmp_path / 'bad_round.json'
    bad.write_text(json.dumps(doctored))
    out = _cli(LEDGER_CLI, '--check', str(bad), '--baseline', base)
    assert out.returncode == 1
    assert 'value' in out.stdout

    cpu = _fixture_round()
    cpu['parsed']['platform'] = 'cpu'
    crossed = tmp_path / 'cpu_round.json'
    crossed.write_text(json.dumps(cpu))
    out = _cli(LEDGER_CLI, '--check', str(crossed), '--baseline', base)
    assert out.returncode == 2


def test_kfac_ledger_selftest():
    out = _cli(LEDGER_CLI, '--selftest')
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------- baseline


def test_build_baseline_deterministic_bytes(tmp_path):
    """TunedPlan artifact convention: same inputs, byte-identical
    file."""
    rounds = [{'parsed': {'platform': 'tpu', 'value': 100.0 + i}}
              for i in range(4)]
    a, b = tmp_path / 'a.json', tmp_path / 'b.json'
    ledger.save_baseline(a, ledger.build_baseline(rounds, sources=['x']))
    ledger.save_baseline(b, ledger.build_baseline(rounds, sources=['x']))
    assert a.read_bytes() == b.read_bytes()
    loaded = ledger.load_baseline(a)
    assert loaded['platform'] == 'tpu'
    assert loaded['keys']['value']['median'] == 101.5


def test_build_baseline_drops_off_provenance_rounds():
    rounds = [
        {'parsed': None},  # a provenance-less driver round
        {'parsed': {'platform': 'tpu', 'value': 10.0}},
        {'parsed': {'platform': 'cpu', 'value': 99.0}},
        {'parsed': {'platform': 'tpu', 'value': 12.0}},
    ]
    base = ledger.build_baseline(rounds)
    assert base['platform'] == 'tpu'
    assert base['n_rounds'] == 2
    assert base['n_dropped_provenance'] == 2
    assert base['keys']['value']['median'] == 11.0
    with pytest.raises(ValueError, match='provenance'):
        ledger.build_baseline([{'parsed': None}])


def test_load_baseline_rejects_foreign_artifacts(tmp_path):
    good = ledger.load_baseline(_fixture('LEDGER.json'))
    wrong_kind = dict(good, kind='tuned_plan')
    p = tmp_path / 'x.json'
    p.write_text(json.dumps(wrong_kind))
    with pytest.raises(ValueError, match='bench_baseline'):
        ledger.load_baseline(p)
    wrong_schema = dict(good, schema=ledger.LEDGER_SCHEMA + 1)
    p.write_text(json.dumps(wrong_schema))
    with pytest.raises(ValueError, match='schema'):
        ledger.load_baseline(p)


# -------------------------------------------------------- run-id threading


def test_jsonl_writer_stamps_header_once(tmp_path):
    p = tmp_path / 'metrics.jsonl'
    hdr = ledger.run_header('run42ab', 'metrics')
    with JSONLWriter(p, run_header=hdr) as sink:
        sink.write({'step': 0, 'loss': 1.0})
    with JSONLWriter(p, run_header=hdr) as sink:  # append: no duplicate
        sink.write({'step': 1, 'loss': 0.9})
    lines = [json.loads(line) for line in p.read_text().splitlines()]
    assert len(lines) == 3
    assert lines[0]['kind'] == 'run_header'
    assert [ln.get('step') for ln in lines[1:]] == [0, 1]
    # and the adapter reads it back
    events = ledger.parse_metrics(p)
    assert {e['run_id'] for e in events} == {'run42ab'}


def test_jsonl_writer_restamps_header_after_rotation(tmp_path):
    p = tmp_path / 'metrics.jsonl'
    hdr = ledger.run_header('run42ab', 'metrics')
    with JSONLWriter(p, run_header=hdr, max_bytes=200) as sink:
        for step in range(12):
            sink.write({'step': step, 'loss': 1.0})
    assert os.path.exists(f'{p}.1')  # rotation happened
    first = json.loads(p.read_text().splitlines()[0])
    assert first.get('kind') == 'run_header'
    assert first['run_id'] == 'run42ab'


def test_jsonl_writer_without_header_unchanged(tmp_path):
    p = tmp_path / 'metrics.jsonl'
    with JSONLWriter(p) as sink:
        sink.write({'step': 0})
    lines = p.read_text().splitlines()
    assert len(lines) == 1 and 'run_header' not in lines[0]


def test_postmortem_manifest_carries_run_id(tmp_path):
    pm = PostmortemWriter(tmp_path / 'pms', engine=None, run_id='run42ab')
    bundle = pm.write_bundle(
        object(), reason='shutdown', record={'step': 3}, history=[], step=3)
    man = json.load(open(os.path.join(bundle, 'MANIFEST.json')))
    assert man['run_id'] == 'run42ab'
    # header-less writers predating the ledger stay valid
    pm = PostmortemWriter(tmp_path / 'pms2', engine=None)
    bundle = pm.write_bundle(
        object(), reason='shutdown', record={'step': 3}, history=[], step=3)
    man = json.load(open(os.path.join(bundle, 'MANIFEST.json')))
    assert man['run_id'] is None


def test_trainer_threads_run_id_into_compile_watch():
    """Construct-only (zero compiles): the Trainer generates/propagates
    the run_id into the engine's compile watch so journal records and
    drained events self-identify."""
    import jax
    import jax.numpy as jnp
    import optax

    import kfac_tpu
    from kfac_tpu import training
    from testing import models

    m = models.TinyModel()
    x, y = models.regression_data(jax.random.PRNGKey(1), n=16)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(registry=reg, compile_watch=True)

    def loss_fn(p, model_state, batch):
        xx, yy = batch
        pred = m.apply({'params': p}, xx)
        return jnp.mean((pred - yy) ** 2), model_state

    trainer = training.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=kfac,
        run_id='run42ab')
    assert trainer.run_id == 'run42ab'
    assert kfac.compile_watcher().run_id == 'run42ab'
    assert trainer.run_header('metrics') == ledger.run_header(
        'run42ab', 'metrics')

    # unset: the Trainer mints one and still threads it
    kfac2 = kfac_tpu.KFACPreconditioner(registry=reg, compile_watch=True)
    trainer2 = training.Trainer(
        loss_fn=loss_fn, optimizer=optax.sgd(0.05), kfac=kfac2)
    assert trainer2.run_id and len(trainer2.run_id) == 12
    assert kfac2.compile_watcher().run_id == trainer2.run_id


# ------------------------------------------------------------------ drift


def test_kfl113_clean_on_committed_doc():
    assert drift.check_ledger_tables() == []


def test_kfl113_catches_doc_drift(tmp_path):
    doc = os.path.join(REPO, 'docs', 'OBSERVABILITY.md')
    with open(doc, encoding='utf-8') as f:
        text = f.read()
    doctored = tmp_path / 'OBSERVABILITY.md'
    doctored.write_text(
        text.replace('| `spike_factor` |', '| `spiek_factor` |'))
    problems = drift.check_ledger_tables(str(doctored))
    assert problems
    assert any('spike_factor' in p for p in problems)


def test_kfl113_registered():
    rules = {r.code for r in drift.core.all_rules()}
    assert 'KFL113' in rules
