#!/usr/bin/env python
"""Posterior serving CLI: selftest of the serving tier.

Drives :class:`kfac_tpu.serving.ServingEngine` — the jitted batched
uncertainty-inference engine over a Laplace export (docs/SERVING.md) —
against a toy last-layer posterior built in-process.

Usage:

    python tools/kfac_serve.py --selftest
        End-to-end sanity pass: toy export -> engine -> warmup, bucketed
        MC/closed-form parity against the direct posterior calls across
        padding buckets, routing/escalation semantics, and the
        zero-recompiles steady-state pin. Exits 0 on success (seconds,
        runs in CI — `make serve`).
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import _common  # noqa: E402

_common.bootstrap()


def _toy_engine(threshold: float | None = None):
    """A trained toy classifier, its last-layer export, and an engine."""
    import jax
    import jax.numpy as jnp

    import kfac_tpu
    from kfac_tpu import health as health_lib
    from kfac_tpu.models import MLP
    from kfac_tpu.serving import ServingConfig, ServingEngine

    m = MLP(features=(8,), num_classes=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 6))
    y = jax.random.randint(jax.random.PRNGKey(2), (64,), 0, 4)
    params = m.init(jax.random.PRNGKey(0), x)['params']
    reg = kfac_tpu.register_model(m, x)
    kfac = kfac_tpu.KFACPreconditioner(
        registry=reg, health=health_lib.HealthConfig(warn=False))

    def loss_fn(p, b):
        xx, yy = b
        logits = m.apply({'params': p}, xx)
        onehot = jax.nn.one_hot(yy, 4)
        return -jnp.mean(jnp.sum(jax.nn.log_softmax(logits) * onehot, -1))

    cap = kfac_tpu.CurvatureCapture(reg)
    _, grads, stats = cap.value_stats_and_grad(loss_fn)(params, (x, y))
    state = kfac.update_factors(kfac.init(), stats)
    post_dir = tempfile.mkdtemp(prefix='kfac_serve_post_')
    kfac_tpu.export_posterior(
        kfac, state, params, post_dir,
        config=kfac_tpu.laplace.LaplaceConfig(mode='last_layer'),
        overwrite=True,
    )
    post = kfac_tpu.load_posterior(post_dir)

    def apply_fn(p, xx):
        return m.apply({'params': p}, xx)

    def phi_fn(p, xx):
        h = xx.reshape(xx.shape[0], -1)
        return jax.nn.relu(h @ p['dense0']['kernel'] + p['dense0']['bias'])

    eng = ServingEngine(
        post, apply_fn, phi_fn=phi_fn,
        config=ServingConfig(
            bucket_granularity=8, max_batch=32, n_samples=4,
            escalated_n_samples=16, variance_threshold=threshold,
            warmup_batches=(8, 32),
        ),
    )
    return post, apply_fn, phi_fn, x, eng


def selftest() -> int:
    """End-to-end checks of the bucketed engine against the posterior."""
    import jax
    import numpy as np

    failures: list[str] = []

    def check(cond: bool, what: str) -> None:
        (failures.append(what) if not cond else None)
        print(f'  {"ok " if cond else "FAIL"} {what}')

    post, apply_fn, phi_fn, x, eng = _toy_engine()
    key = jax.random.PRNGKey(7)
    warm = eng.warmup(x_spec=x[:1], key=key)
    check(warm['buckets'] == [8, 32], 'warmup compiles the config buckets')

    # bucketed MC parity vs the direct (unbucketed) posterior formula,
    # across batch sizes that pad, fill, and chunk the buckets
    def ref_mc(xx, k, n):
        keys = jax.random.split(k, n)

        def one(kk):
            return jax.nn.softmax(apply_fn(post.sample_params(kk), xx))

        return jax.vmap(one)(keys).mean(0)

    for b in (3, 8, 13, 32, 50):
        got = np.asarray(eng.mc_probs(x[:b], key, n_samples=4))
        ref = np.asarray(jax.jit(ref_mc, static_argnums=2)(x[:b], key, 4))
        check(
            np.allclose(got, ref, rtol=1e-6, atol=1e-7),
            f'MC parity vs direct posterior at batch {b} '
            f'(maxdiff {np.abs(got - ref).max():.2e})',
        )

    # closed-form parity vs the posterior's own linearized variance
    probs, var = eng.closed_form(x[:13])
    ref_probs = np.asarray(jax.nn.softmax(apply_fn(post.params, x[:13])))
    ref_var = np.asarray(post.linearized_variance(phi_fn(post.params, x[:13])))
    check(
        np.allclose(np.asarray(probs), ref_probs, rtol=1e-6),
        'closed-form probs match the MAP apply',
    )
    check(
        np.allclose(np.asarray(var), ref_var, rtol=1e-6, atol=1e-7),
        f'closed-form variance matches linearized_variance '
        f'(maxdiff {np.abs(np.asarray(var) - ref_var).max():.2e})',
    )

    # steady state: every served size above hit a warmed bucket
    check(
        eng.recompiles_after_warmup() == 0,
        'recompiles_after_warmup == 0 across all served sizes',
    )
    eng.close()

    # routing: a threshold at the median escalates some rows, answers
    # keep their shape, and escalated rows carry the MC answer
    _, _, _, x2, eng2 = _toy_engine(threshold=1e-9)  # everything escalates
    eng2.warmup(x_spec=x2[:1], key=key)
    res = eng2.serve(x2[:8], key=key, path='auto')
    mc = np.asarray(eng2.mc_probs(x2[:8], key, n_samples=16))
    check(bool(np.asarray(res.escalated).all()),
          'tiny threshold escalates every row')
    check(
        np.allclose(np.asarray(res.probs), mc, rtol=1e-6),
        'escalated rows carry the escalated-MC answer',
    )
    check(eng2.recompiles_after_warmup() == 0,
          'routing path stays at zero recompiles')
    eng2.close()

    if failures:
        print(f'kfac_serve selftest: {len(failures)} FAILURES')
        return 1
    print('kfac_serve selftest: ok')
    return 0


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument('--selftest', action='store_true', required=True,
                   help='end-to-end parity + recompile pin (exit 0 on ok)')
    p.parse_args(argv)
    return selftest()


if __name__ == '__main__':
    sys.exit(main())
