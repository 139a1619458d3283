"""Jitted batched uncertainty inference over a loaded Laplace posterior.

The training half of the repo distributes K-FAC curvature; this module
is the serving half: a :class:`ServingEngine` wraps a loaded
:class:`~kfac_tpu.laplace.LaplacePosterior` and answers prediction
requests with calibrated uncertainty under production constraints —
fixed compiled shapes, AOT warm start, per-request metrics.

Three design points carry the engine:

- **Padding buckets.** Arbitrary request batch sizes are rounded up to
  a small fixed set of size classes with the ``size_class`` grammar the
  KAISA layout already uses for factor dims
  (``kfac_tpu/parallel/kaisa.py``), and the batch is zero-padded to the
  class. Every layer the posterior serves is row-independent (dense /
  conv apply, per-row softmax), so padded rows cannot perturb real
  rows: the sliced-back outputs are bit-identical to an unpadded
  evaluation of the same program. Steady-state serving therefore holds
  the compile count fixed — one program per (bucket, path).
- **AOT warm start.** Each path dispatches through the PR-17
  CompileWatch machinery (``lower().compile()`` keyed by argument
  fingerprint), so :meth:`ServingEngine.warmup` pre-compiles the
  bucket set before the first request, the persistent compile cache
  turns a replica restart into cache hits, and
  ``recompiles_after_warmup`` is a measurable counter rather than a
  hope.
- **Uncertainty-aware routing.** The closed-form last-layer variance
  is orders of magnitude cheaper than Monte-Carlo sampling; the
  ``auto`` path computes it first and escalates only the requests
  whose variance clears ``ServingConfig.variance_threshold`` to the
  ``escalated_n_samples`` MC predictive.

See docs/SERVING.md for the walkthrough.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from kfac_tpu.laplace import posterior as posterior_lib
from kfac_tpu.observability import compile_watch as compile_watch_lib
from kfac_tpu.observability import ledger as ledger_lib
from kfac_tpu.observability import sinks as sinks_lib
from kfac_tpu.parallel.kaisa import size_class
from kfac_tpu.serving import config as config_lib

#: CompileWatch entry-name prefixes for the two compiled paths. Each
#: (bucket, sample-count) program gets its own entry
#: (``serving.mc.b32.n8``, ``serving.cf.b32``) holding exactly one
#: fingerprint, so ``watch.recompile_count()`` across the engine is the
#: steady-state pin: 0 once every served size hits a warmed bucket.
MC_ENTRY = 'serving.mc'
CF_ENTRY = 'serving.cf'


@dataclasses.dataclass(frozen=True)
class ServeResult:
    """One answered request batch.

    Attributes:
        probs: (batch, classes) predictive probabilities — MC mean
            softmax on the ``mc`` path, MAP softmax (or the escalated
            mix) on ``closed_form``/``auto``.
        variance: (batch, classes) closed-form per-logit variance, or
            ``None`` on the pure ``mc`` path.
        escalated: (batch,) bool mask of requests the ``auto`` router
            escalated to the MC path; ``None`` when routing was off.
        path: the path the batch was served on (``'mc'``,
            ``'closed_form'``, or ``'auto'``).
        bucket: padded batch size(s) the compiled program(s) ran at.
        latency_s: host wall-clock for the batch, blocked to
            completion.
    """

    probs: jax.Array
    variance: jax.Array | None
    escalated: jax.Array | None
    path: str
    bucket: tuple[int, ...]
    latency_s: float


class ServingEngine:
    """Batched posterior inference with fixed compiled shapes.

    Args:
        posterior: a loaded (or freshly exported)
            :class:`~kfac_tpu.laplace.LaplacePosterior`.
        apply_fn: ``apply_fn(params, x) -> logits`` — the model forward
            the posterior was exported against.
        phi_fn: ``phi_fn(params, x) -> phi`` penultimate features (the
            inputs TO the covered last layer). Required for the
            ``closed_form`` and ``auto`` paths of a ``last_layer``
            posterior; irrelevant otherwise.
        config: :class:`~kfac_tpu.serving.ServingConfig` knobs.
        run_id: shared ledger run id threaded into the serving-metrics
            stream header (minted when omitted and metrics are on).
        watch: a :class:`~kfac_tpu.observability.compile_watch.
            CompileWatch` to report compiles into; a private one is
            created when omitted.
    """

    def __init__(
        self,
        posterior: posterior_lib.LaplacePosterior,
        apply_fn: Callable[[Any, jax.Array], jax.Array],
        phi_fn: Callable[[Any, jax.Array], jax.Array] | None = None,
        config: config_lib.ServingConfig | None = None,
        run_id: str | None = None,
        watch: compile_watch_lib.CompileWatch | None = None,
    ) -> None:
        self.posterior = posterior
        self.apply_fn = apply_fn
        self.phi_fn = phi_fn
        self.config = config or config_lib.ServingConfig()
        self.run_id = run_id
        self.watch = watch or compile_watch_lib.CompileWatch(
            compile_watch_lib.CompileWatchConfig())
        self._writer: sinks_lib.JSONLWriter | None = None
        self._wrapped: dict[str, Any] = {}

        def mc_raw(x: jax.Array, key: jax.Array, n_samples: int):
            keys = jax.random.split(key, n_samples)
            probs = jax.vmap(
                lambda k: jax.nn.softmax(
                    apply_fn(posterior.sample_params(k), x))
            )(keys)
            return probs.mean(axis=0)

        self._mc_jit = jax.jit(mc_raw, static_argnames=('n_samples',))

        self._cf_jit = None
        if phi_fn is not None and posterior.config.mode == 'last_layer':

            def cf_raw(x: jax.Array):
                probs = jax.nn.softmax(apply_fn(posterior.params, x))
                var = posterior.linearized_variance(phi_fn(posterior.params, x))
                return probs, var

            self._cf_jit = jax.jit(cf_raw)

    def _watched_mc(self, c: int, n: int) -> Any:
        """The watched MC program for bucket ``c`` at ``n`` samples —
        one entry per (bucket, samples) pair, one fingerprint each."""
        entry = f'{MC_ENTRY}.b{c}.n{n}'
        wrapped = self._wrapped.get(entry)
        if wrapped is None:
            wrapped = self.watch.wrap(
                entry, self._mc_jit, static_argnames=('n_samples',))
            self._wrapped[entry] = wrapped
        return wrapped

    def _watched_cf(self, c: int) -> Any:
        entry = f'{CF_ENTRY}.b{c}'
        wrapped = self._wrapped.get(entry)
        if wrapped is None:
            wrapped = self.watch.wrap(entry, self._cf_jit)
            self._wrapped[entry] = wrapped
        return wrapped

    # ------------------------------------------------------------ buckets

    @property
    def closed_form_available(self) -> bool:
        """Whether this engine can serve the closed-form/auto paths."""
        return self._cf_jit is not None

    def bucket(self, n: int) -> int:
        """The padded batch size a request batch of ``n`` rows runs at."""
        if n < 1:
            raise ValueError(f'request batch must be >= 1 rows, got {n}')
        n = min(n, self.config.max_batch)
        return size_class(n, self.config.bucket_granularity)

    def _chunks(self, n: int) -> list[tuple[int, int]]:
        """(start, length) request chunks, each within ``max_batch``."""
        cap = self.config.max_batch
        return [(s, min(cap, n - s)) for s in range(0, n, cap)]

    def _pad(self, x: jax.Array, c: int) -> jax.Array:
        if x.shape[0] == c:
            return x
        pad = [(0, c - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, pad)

    def _base_samples(self, n_samples: int | None) -> int:
        if n_samples is not None:
            return int(n_samples)
        if self.config.n_samples is not None:
            return int(self.config.n_samples)
        return int(self.posterior.config.n_samples)

    # -------------------------------------------------------------- paths

    def mc_probs(
        self,
        x: jax.Array,
        key: jax.Array,
        n_samples: int | None = None,
    ) -> jax.Array:
        """Bucketed MC posterior-predictive probabilities.

        Pads each request chunk to its size class, runs the compiled
        program, and slices the real rows back out. The weight draws
        depend only on ``key`` (never on ``x``), so every chunk reuses
        the same ``key`` and the result equals the unbucketed
        evaluation row for row.
        """
        n = self._base_samples(n_samples)
        outs = []
        for start, length in self._chunks(x.shape[0]):
            chunk = x[start:start + length]
            c = self.bucket(length)
            padded = self._watched_mc(c, n)(
                self._pad(chunk, c), key, n_samples=n)
            outs.append(padded[:length])
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)

    def closed_form(
        self, x: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        """Bucketed closed-form path: (MAP probs, per-logit variance)."""
        if self._cf_jit is None:
            raise ValueError(
                'closed-form serving needs a last_layer posterior and a '
                'phi_fn (penultimate-feature extractor); this engine has '
                f'mode={self.posterior.config.mode!r}, '
                f'phi_fn={"set" if self.phi_fn else "None"}'
            )
        probs, var = [], []
        for start, length in self._chunks(x.shape[0]):
            chunk = x[start:start + length]
            c = self.bucket(length)
            p, v = self._watched_cf(c)(self._pad(chunk, c))
            probs.append(p[:length])
            var.append(v[:length])
        if len(probs) == 1:
            return probs[0], var[0]
        return jnp.concatenate(probs, axis=0), jnp.concatenate(var, axis=0)

    # -------------------------------------------------------------- serve

    def serve(
        self,
        x: jax.Array,
        key: jax.Array | None = None,
        path: str = 'auto',
        n_samples: int | None = None,
    ) -> ServeResult:
        """Answer one request batch on the named path.

        ``'mc'`` runs the Monte-Carlo predictive (``key`` required);
        ``'closed_form'`` returns MAP probabilities plus the linearized
        variance; ``'auto'`` serves closed-form and escalates requests
        whose max per-logit variance clears
        ``ServingConfig.variance_threshold`` to an
        ``escalated_n_samples`` MC pass (``key`` required when
        escalation is enabled). Emits one serving-metrics record when
        ``metrics_path`` is configured.
        """
        if path not in config_lib.PATHS:
            raise ValueError(
                f'path must be one of {config_lib.PATHS}, got {path!r}')
        if path == 'auto' and not self.closed_form_available:
            path = 'mc'
        t0 = time.perf_counter()
        n_requests = int(x.shape[0])
        buckets = tuple(self.bucket(length)
                        for _, length in self._chunks(n_requests))
        variance = escalated = None
        n = 0
        if path == 'mc':
            if key is None:
                raise ValueError('the mc path needs a sampling key')
            n = self._base_samples(n_samples)
            probs = self.mc_probs(x, key, n)
        else:
            probs, variance = self.closed_form(x)
            threshold = self.config.variance_threshold
            if path == 'auto' and threshold is not None:
                if key is None:
                    raise ValueError(
                        'auto routing with a variance_threshold needs a '
                        'sampling key for the escalated MC pass')
                escalated = jnp.max(variance, axis=-1) > threshold
                if bool(jnp.any(escalated)):
                    # fixed-shape escalation: the whole bucket runs the
                    # escalated program and the router selects per row —
                    # no data-dependent shapes reach the compiler
                    n = int(self.config.escalated_n_samples)
                    mc = self.mc_probs(x, key, n)
                    probs = jnp.where(escalated[:, None], mc, probs)
        jax.block_until_ready(probs)
        latency_s = time.perf_counter() - t0
        result = ServeResult(
            probs=probs, variance=variance, escalated=escalated,
            path=path, bucket=buckets, latency_s=latency_s)
        self._emit(result, n_requests, n)
        return result

    # ------------------------------------------------------------- warmup

    def warmup(
        self,
        batch_sizes: tuple[int, ...] | None = None,
        key: jax.Array | None = None,
        x_spec: jax.Array | None = None,
        n_samples: int | None = None,
    ) -> dict[str, Any]:
        """Pre-compile every (bucket, path) program before traffic.

        ``x_spec`` is one example request row batch (any batch size) —
        its trailing shape and dtype define the request schema; zeros
        at each bucket size drive the compiles. Returns the measured
        warm-start report: wall-clock, buckets compiled, per-entry
        compile counts, and the persistent-cache hit/miss delta (a
        warm replica restart shows up as hits, docs/SERVING.md
        "Warm start").
        """
        if x_spec is None:
            raise ValueError('warmup needs x_spec (one example batch)')
        sizes = tuple(batch_sizes if batch_sizes is not None
                      else self.config.warmup_batches)
        if not sizes:
            return {'seconds': 0.0, 'buckets': [], 'compiles': {},
                    'persistent_cache': {}}
        key = key if key is not None else jax.random.PRNGKey(0)
        counters = compile_watch_lib.persistent_cache_counters()
        before = counters.snapshot()
        compiles0 = self.watch.compile_count()
        buckets = sorted({self.bucket(int(b)) for b in sizes})
        n = self._base_samples(n_samples)
        t0 = time.perf_counter()
        for c in buckets:
            zeros = jnp.zeros((c,) + x_spec.shape[1:], x_spec.dtype)
            jax.block_until_ready(
                self._watched_mc(c, n)(zeros, key, n_samples=n))
            if self.config.variance_threshold is not None \
                    and self.closed_form_available:
                esc = int(self.config.escalated_n_samples)
                jax.block_until_ready(
                    self._watched_mc(c, esc)(zeros, key, n_samples=esc))
            if self.closed_form_available:
                jax.block_until_ready(self._watched_cf(c)(zeros))
        seconds = time.perf_counter() - t0
        after = counters.snapshot()
        return {
            'seconds': round(seconds, 4),
            'buckets': buckets,
            'compiles': self.watch.compile_count() - compiles0,
            'persistent_cache': {
                'hits': after['persistent_cache_hits']
                - before['persistent_cache_hits'],
                'misses': after['persistent_cache_misses']
                - before['persistent_cache_misses'],
                'dir': after.get('persistent_cache_dir'),
            },
        }

    def recompiles_after_warmup(self) -> int:
        """Compiles beyond the first per (entry, fingerprint) — the
        steady-state pin: 0 once every served size hits a warmed
        bucket."""
        return self.watch.recompile_count()

    # ------------------------------------------------------------ metrics

    def _emit(self, result: ServeResult, n_requests: int,
              n_samples: int) -> None:
        path = self.config.metrics_path
        if path is None:
            return
        if self._writer is None:
            if self.run_id is None:
                self.run_id = ledger_lib.new_run_id()
            self._writer = sinks_lib.JSONLWriter(
                path, append=True,
                run_header=ledger_lib.run_header(self.run_id, 'serving'))
        n_escalated = (int(jnp.sum(result.escalated))
                       if result.escalated is not None else 0)
        self._writer.write({
            'kind': 'serve',
            'path': result.path,
            'requests': n_requests,
            'bucket': list(result.bucket),
            'n_samples': n_samples,
            'n_escalated': n_escalated,
            'latency_ms': round(result.latency_s * 1e3, 3),
            't': time.time(),
        })

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None

    def __enter__(self) -> 'ServingEngine':
        return self

    def __exit__(self, *exc: Any) -> None:
        self.close()
