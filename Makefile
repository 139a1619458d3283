# CI entry points — the counterpart of the reference's tox.ini
# (/root/reference/tox.ini:1-21) for a non-pip-installed JAX library.
#
# Two tiers (pyproject.toml markers):
#   test-fast  pre-commit tier: `-m 'not slow'`
#   test       full suite — measured 7:45 warm-cache on a 1-core host,
#              inside the reference's 15-minute CI budget
#              (.github/workflows/tests.yml:12)
#
# All targets pin the host platform: the 8-virtual-device CPU mesh the
# suite is written against. The chip is reached one way only —
# `python chip_smoke.py` on a machine that has one.

PY ?= python
TEST_ENV = JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8"

.PHONY: test test-fast test-unit test-integration faults async chaos compilewatch ledger serve obs prof tune resilience lint lint-ir lint-pod inspect native

test:
	$(TEST_ENV) $(PY) -m pytest tests/ -q

test-fast:
	$(TEST_ENV) $(PY) -m pytest tests/ -q -m 'not slow'

# unit/integration partition the suite for CI (the reference's
# tests.yml + integration.yml split); `test` is the run-everything entry
test-unit:
	$(TEST_ENV) $(PY) -m pytest tests/ -q --ignore=tests/integration

test-integration:
	$(TEST_ENV) $(PY) -m pytest tests/integration/ -q

# numerical-health sentinel fault-injection suite (includes its slow
# distributed cases; see docs/ROBUSTNESS.md)
faults:
	$(TEST_ENV) $(PY) -m pytest tests/ -q -m faults

# async curvature refresh: double-buffered inverse suite (sliced +
# host backends, staleness/quarantine/checkpoint semantics); the
# named-scope lint covers the async entry points too
async:
	$(TEST_ENV) $(PY) -m pytest tests/test_async_inverse.py -q
	$(TEST_ENV) $(PY) tools/lint_named_scopes.py

# pod-scale chaos harness: CLI selftest (processless reconcile/grammar
# checks) + the chaos suite including the deterministic 4-proc scripted
# storm against a real gloo pod; the 16-proc seeded storm rides behind
# the `slow` marker (see docs/ROBUSTNESS.md "Chaos harness")
chaos:
	$(TEST_ENV) $(PY) tools/kfac_chaos.py --selftest
	$(TEST_ENV) $(PY) -m pytest tests/test_chaos.py -q -m 'not slow'

# measurement-truth layer (docs/OBSERVABILITY.md "Measurement truth"):
# a real microbench smoke sweep on the CPU backend (fori_loop one-
# dispatch provenance + latency-floor verdicts over an actual size
# sweep) and the measurement + calibration test suites
prof:
	$(TEST_ENV) $(PY) tools/tpu_microbench.py --smoke --no-pallas \
		--sizes 128 256 --iters 2 --rows 512 > /tmp/kfac_prof_micro.jsonl
	$(TEST_ENV) $(PY) -m pytest tests/test_measurement.py \
		tests/test_calibration.py -q

# compile & memory truth (docs/OBSERVABILITY.md "Compile & memory
# truth"): recompile attribution / XLA memory accounting / mid-compile
# heartbeat suite on both engines, plus the kfac_inspect selftest that
# covers the "died compiling X" journal verdict
compilewatch:
	$(TEST_ENV) $(PY) -m pytest tests/test_compile_watch.py -q -m 'not slow'
	$(PY) tools/kfac_inspect.py --selftest

# unified run ledger: adapter/correlation/sentinel suite, the
# kfac_ledger CLI selftest, and the committed-fixture timeline +
# sentinel runs (byte-stable golden, provenance-matched check)
ledger:
	$(TEST_ENV) $(PY) -m pytest tests/test_ledger.py -q -m 'not slow'
	$(PY) tools/kfac_ledger.py --selftest
	$(PY) tools/kfac_ledger.py --timeline tests/data/mini_ledger >/dev/null
	$(PY) tools/kfac_ledger.py --check tests/data/mini_ledger/bench_round.json \
		--baseline tests/data/mini_ledger/LEDGER.json

# posterior serving tier: bucketed-engine suite (MC/closed-form parity
# across padding buckets, routing, zero-recompile pins, KFL114) and the
# kfac_serve CLI selftest (see docs/SERVING.md)
serve:
	$(TEST_ENV) $(PY) -m pytest tests/test_serving.py -q
	$(TEST_ENV) $(PY) tools/kfac_serve.py --selftest

# telemetry spine: observability + flight-recorder test suites, the
# measurement-truth layer (prof: dispatch-free microbench,
# calibration), the compile & memory truth layer
# (compilewatch: recompile attribution, XLA memory accounting,
# mid-compile heartbeats), the unified static-analysis pass (which
# includes the named-scope, metric-key, plan-schema, calibration-knob,
# topology-knob, chaos-knob and compile-watch-knob lints as
# KFL101-KFL103/KFL108/KFL109/KFL111/KFL112 plus the
# IR-tier smoke pass via lint-ir), the unified run ledger (ledger:
# adapters, correlation timeline, perf-regression sentinel, KFL113),
# the posterior serving tier (serve: bucketed-engine parity + routing +
# recompile pins + the kfac_serve selftest, KFL114), and the
# kfac_inspect analysis selftest (see docs/OBSERVABILITY.md)
obs: async lint chaos prof compilewatch ledger serve
	$(TEST_ENV) $(PY) -m pytest tests/test_observability.py \
		tests/test_flight_recorder.py -q
	$(PY) tools/kfac_inspect.py --selftest

# kfaclint IR tier alone (KFL201-KFL205), smoke profile: traces only
# the dense-transport d=64 eigen config so wall-clock stays bounded;
# the full strategy x method x transport matrix runs behind the `slow`
# marker in tests/test_kfaclint_ir.py (see docs/ANALYSIS.md "IR tier")
lint-ir:
	$(TEST_ENV) $(PY) tools/kfaclint.py --ir --smoke

# kfaclint pod tier alone (KFL301-KFL305): cross-rank SPMD protocol
# verification — rank-forking abstract interpretation plus the
# protocol-table model check (see docs/ANALYSIS.md "Pod tier")
lint-pod:
	$(TEST_ENV) $(PY) tools/kfaclint.py --pod

# kfaclint: AST rules (KFL001-KFL005) + docs-vs-code drift rules
# (KFL100-KFL109) + IR rules (KFL201-KFL205, smoke profile) + pod rules
# (KFL301-KFL305) + the analyzer's own fixture selftest and test suites
# (see docs/ANALYSIS.md). The --all pass runs under `timeout` as a
# wall-clock budget assertion: every tier together must stay a
# pre-commit-sized check, not a test suite
lint: lint-ir lint-pod
	$(TEST_ENV) timeout -k 10 300 $(PY) tools/kfaclint.py --all --smoke
	$(TEST_ENV) $(PY) tools/kfaclint.py --selftest
	$(TEST_ENV) $(PY) -m pytest tests/test_kfaclint.py \
		tests/test_kfaclint_ir.py tests/test_kfaclint_pod.py \
		-q -m 'not slow'

# layout autotuner: test suite, the plan-schema doc lint, and the
# end-to-end kfac_tune pipeline selftest (see docs/AUTOTUNE.md)
tune:
	$(TEST_ENV) $(PY) -m pytest tests/test_autotune.py -q
	$(TEST_ENV) $(PY) tools/lint_plan_schema.py
	$(TEST_ENV) $(PY) tools/kfac_tune.py --selftest

# preemption-safe training: checkpoint-autopilot suite (includes the
# slow real-kill subprocess test) and the signal-semantics doc lint
# (see docs/ROBUSTNESS.md "Preemption & resume")
resilience:
	$(TEST_ENV) $(PY) -m pytest tests/test_resilience.py -q
	$(TEST_ENV) $(PY) tools/lint_signals.py

# offline triage: divergence timeline from a metrics JSONL or a
# flight-recorder postmortem bundle directory
#   make inspect BUNDLE=postmortems/postmortem-step00000042-skip
inspect:
	$(PY) tools/kfac_inspect.py $(BUNDLE)

# the loader self-builds (and caches) on first use; this just forces it
native:
	$(TEST_ENV) $(PY) -c "from kfac_tpu.utils.native_loader import _load_lib; _load_lib(); print('native loader built and loaded')"
