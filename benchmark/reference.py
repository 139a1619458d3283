"""The plain reference's first three steps of a job, from the same weights
and batches as the program: what ``correct`` compares the program with.

Step 0 captures the factors, builds the damped inverses and preconditions;
steps 1 and 2 precondition with those inverses (factors every 10 steps,
inverses every 100: neither moves again so early). It returns host numbers
only: each step's loss, the norm of every leaf of the first gradient as
the optimizer's momentum sees it, and of the parameters' change after the
three steps.
"""

from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp

from benchmark.refs import kfac

STEPS = 3


def _flat_layers(factors: dict, layers) -> dict:
    return {name: factors[name] for name in layers}


class Reference:
    """The plain reference of one cell. Its programs are built at their
    first call; a run drops it, and they unload, before the program's
    first step (``harness.reference_steps``)."""

    def __init__(self, kind: str, config: dict, workload: dict) -> None:
        self.ref = importlib.import_module(f'benchmark.refs.{kind}')
        self.loss_and_grads, self.loss_grads_factors = self.ref.make(config)
        self.kfac, self.opt = workload['kfac'], config['optimizer']
        k = self.kfac
        if int(k['factor_update_steps']) < STEPS or (
            int(k['inv_update_steps']) < STEPS
        ):
            raise ValueError(
                'the reference follows a capture step and two plain steps: '
                f'cadence {k} moves the factors again inside them'
            )

    def lr_at(self, step: int) -> float:
        opt = self.opt
        return opt['lr'] * min(1.0, (step + 1) / max(1, opt['warmup_steps']))

    def first_steps(self, params, batches) -> dict:
        """``params``: the seed's weights, placed; ``batches``: the first
        ``STEPS`` batches, placed. Nothing of either is modified."""
        k, opt = self.kfac, self.opt
        layers = self.ref.kfac_layers(params)
        t0 = time.perf_counter()
        loss, grads, a, g = jax.block_until_ready(
            self.loss_grads_factors(params, batches[0])
        )
        t1 = time.perf_counter()
        a_inv = kfac.first_inverses(
            _flat_layers(a, layers), k['factor_decay'], k['damping']
        )
        g_inv = kfac.first_inverses(
            _flat_layers(g, layers), k['factor_decay'], k['damping']
        )
        del a, g
        t2 = time.perf_counter()
        p = params
        trace = jax.tree_util.tree_map(jnp.zeros_like, params)
        losses, first_grad = [], None
        for step in range(STEPS):
            if step:
                loss, grads = self.loss_and_grads(p, batches[step])
            losses.append(float(loss))
            lr = self.lr_at(step)
            grads = kfac.precondition(
                grads, a_inv, g_inv, lr, k['kl_clip'], layers
            )
            p, trace = kfac.sgd_step(
                p, trace, grads, lr, opt['momentum'],
                opt.get('weight_decay', 0.0), opt.get('clip_global_norm'),
            )
            if step == 0:
                first_grad = jax.device_get(kfac.leaf_norms(trace))
        update = jax.device_get(kfac.leaf_norms(
            jax.tree_util.tree_map(jnp.subtract, p, params)
        ))
        self.seconds = {
            'loss_grads_factors': round(t1 - t0, 1),
            'inverses': round(t2 - t1, 1),
            'three_updates': round(time.perf_counter() - t2, 1),
        }
        return {
            'losses': losses,
            'first_grad_norms': {n: float(v) for n, v in first_grad.items()},
            'update_norms': {n: float(v) for n, v in update.items()},
        }
