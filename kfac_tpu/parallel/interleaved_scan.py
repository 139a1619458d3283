"""Single-slot interleaved 1F1B pipeline scan (Megatron virtual stages).

:class:`InterleavedPipelinedLM` assigns each pipeline rank ``v`` model
chunks (logical stage ``s = c*p + r`` lives on rank ``r`` as its chunk
``c``) and drives them with the SINGLE-SLOT schedule tables from
:func:`kfac_tpu.parallel.interleaved.generate_single_slot`: one F *or* B
chunk execution per rank per tick, so fill/drain are paid in chunk units
and the per-rank bubble drops to ``2*(p-1)/v`` stage-units — the full
Megatron reduction (Narayanan et al. 2021, §2.2), which the 2-slot
combined scan of :class:`kfac_tpu.parallel.pipeline.PipelinedLM`
(schedule='1f1b') structurally caps at ~25%.

The reference rides DeepSpeed's PipelineEngine and has no interleaving;
this is the beyond-reference pipeline milestone.

Execution model (one ``lax.scan`` over ticks inside one ``shard_map``):

- Stage parameters stack RANK-MAJOR: stack index ``r*v + c`` holds
  logical stage ``c*p + r``, so ``P(pipe)`` on the leading axis gives
  each rank exactly its ``v`` chunks. :func:`logical_to_stack` converts.
- Each tick looks up this rank's ``(kind, chunk, mb, slot)`` in the
  static tables (a closed-over constant indexed by ``axis_index``) and
  ``lax.switch``es between an idle, a forward (plain chunk apply), and a
  backward body (chunk recompute under ``jax.vjp`` with the capture
  interceptor + g-taps — identical semantics to the 2-slot scan). The
  LAST logical stage's backward recomputes head+loss+cotangent in-op
  from the saved stage input, so it needs no external cotangent.
- Activations and cotangents ``ppermute`` between ticks UNCONDITIONALLY
  (collectives must run uniformly across ranks; idle/other-kind ticks
  send zeros flagged invalid) into small per-chunk inboxes whose depths
  the schedule generator proved sufficient (messages per (rank, chunk)
  are produced and consumed in microbatch order, so ``mb % depth``
  never collides).
- Stage inputs persist in a residual ring whose slots the generator
  allocated per-op (``slot`` column) — no runtime free-list, and the
  ring size is exactly the schedule's true in-flight maximum.

Memory: the ring holds ``2*(p-1) + (v-1)*p + 1`` stage inputs (the
interleaved warmup depth) vs the 2-slot scan's ``2*p - 1`` — deeper
in-flight is the price of the smaller bubble, exactly as in Megatron.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from kfac_tpu.layers import capture as capture_lib
from kfac_tpu.parallel import interleaved as interleaved_lib
from kfac_tpu.parallel import pipeline as pipeline_lib
from kfac_tpu.parallel.pipeline import PIPE_AXIS


def logical_to_stack(p: int, v: int, s: int) -> int:
    """Stack index (rank-major ``r*v + c``) of logical stage ``s = c*p + r``."""
    return (s % p) * v + s // p


@dataclasses.dataclass
class InterleavedPipelinedLM(pipeline_lib.PipelinedLM):
    """Decoder LM pipelined with ``virtual_chunks`` model chunks per rank
    under the single-slot interleaved 1F1B schedule.

    Same surface as :class:`PipelinedLM` (init / loss_and_stats /
    PipelineKFAC integration); ``n_stages`` becomes the TOTAL logical
    stage count ``p * virtual_chunks`` and ``n_microbatches`` must be a
    positive multiple of the rank count ``p`` (Megatron's constraint).
    """

    virtual_chunks: int = 2

    def _executes_interleaved(self) -> bool:
        return True

    def _chunks_per_rank(self) -> int:
        # consulted by PipelinedLM.__post_init__ BEFORE it builds the
        # stage module/registry, so construction happens exactly once
        # with n_stages = p * virtual_chunks
        if self.virtual_chunks < 1:
            raise ValueError(
                f'virtual_chunks must be >= 1, got {self.virtual_chunks}'
            )
        return self.virtual_chunks

    def __post_init__(self) -> None:
        super().__post_init__()
        self.p_ranks = int(self.mesh.shape[PIPE_AXIS])
        self.schedule = 'interleaved'
        self._sched = interleaved_lib.generate_single_slot(
            self.p_ranks, self.virtual_chunks, self.n_microbatches
        )

    def apply(self, params, tokens, gstats=None):
        raise NotImplementedError(
            'the forward-only apply() path runs the plain per-rank '
            'pipeline and does not understand virtual chunks; use '
            'loss_and_stats (the single-slot scan) or a PipelinedLM'
        )

    # ------------------------------------------------------------- body

    def _body_interleaved(
        self, stage_params, head_params, lnf_params, x_feed, t_feed, gstats
    ):
        """shard_map body: the single-slot schedule over all ticks.

        Local views: ``stage_params`` / ``gstats`` carry this rank's ``v``
        chunks on their leading axis; ``x_feed``/``t_feed`` are the
        microbatch feeds; outputs mirror
        :meth:`PipelinedLM._body_1f1b` with per-chunk leading axes.
        """
        sp = stage_params
        gst = gstats
        p = self.p_ranks
        v = self.virtual_chunks
        m = self.n_microbatches
        sched = self._sched
        ring, d_act, d_cot = sched.ring, sched.act_depth, sched.cot_depth
        registry = self.stage_registry
        all_axes = (PIPE_AXIS,) + self.data_axes
        if self.data_axes:
            vary = lambda t: jax.tree_util.tree_map(
                lambda x: jax.lax.pcast(x, self.data_axes, to='varying'), t
            )
            sp, gst = vary(sp), vary(gst)
            x_feed = jax.lax.pcast(x_feed, (PIPE_AXIS,), to='varying')
            t_feed = jax.lax.pcast(t_feed, (PIPE_AXIS,), to='varying')
        head_params, lnf_params = jax.tree_util.tree_map(
            lambda x: jax.lax.pcast(x, all_axes, to='varying'),
            (head_params, lnf_params),
        )
        rank = jax.lax.axis_index(PIPE_AXIS)
        if self.data_axes:
            rank = jax.lax.pcast(rank, self.data_axes, to='varying')
        b_m, s_len, d = x_feed.shape[1:]
        last_stage = p * v - 1
        dp = 1
        for ax in self.data_axes:
            dp *= int(self.mesh.shape[ax])
        total_tokens = float(m * b_m * s_len * dp)
        fwd_perm = [(j, (j + 1) % p) for j in range(p)]
        bwd_perm = [(j, (j - 1) % p) for j in range(p)]
        # this rank's tick table: (ticks, 4) — static array, varying index
        ops_r = jnp.take(jnp.asarray(sched.ops), rank, axis=1)

        head_loss = self._make_head_loss(total_tokens)
        zeros_like_vary = self._zeros_like_vary(all_axes)
        zero_a = {
            name: jnp.zeros((v,) + h.a_factor_shape, jnp.float32)
            for name, h in registry.layers.items()
        }
        carry0 = dict(
            act_in=zeros_like_vary(
                jnp.zeros((v, d_act, b_m, s_len, d), self.dtype)
            ),
            cot_in=zeros_like_vary(
                jnp.zeros((v, d_cot, b_m, s_len, d), self.dtype)
            ),
            resid=zeros_like_vary(
                jnp.zeros((ring, b_m, s_len, d), self.dtype)
            ),
            xbar=zeros_like_vary(jnp.zeros((m, b_m, s_len, d), self.dtype)),
            loss=zeros_like_vary(jnp.zeros((), jnp.float32)),
            sgrads=zeros_like_vary(
                jax.tree_util.tree_map(jnp.zeros_like, sp)
            ),
            hgrads=zeros_like_vary(
                jax.tree_util.tree_map(
                    lambda x: jnp.zeros_like(x, jnp.float32), head_params
                )
            ),
            lgrads=zeros_like_vary(
                jax.tree_util.tree_map(
                    lambda x: jnp.zeros_like(x, jnp.float32), lnf_params
                )
            ),
            a_acc=zeros_like_vary(zero_a),
            g_acc=zeros_like_vary(
                {k: jnp.zeros_like(x) for k, x in gst.items()}
            ),
            n_b=zeros_like_vary(jnp.zeros((v,), jnp.float32)),
            # per-rank (executed F, executed B, idle) tick counters —
            # incremented from the live op kind each tick, so the counts
            # come out of the executed scan, not the static tables
            ticks=zeros_like_vary(jnp.zeros((3,), jnp.int32)),
        )
        zero_msg = zeros_like_vary(jnp.zeros((b_m, s_len, d), self.dtype))
        zero_meta = zeros_like_vary(jnp.zeros((3,), jnp.int32))

        def tick(carry, op):
            kind, chunk, mb, slot = op[0], op[1], op[2], op[3]
            chunk_c = jnp.clip(chunk, 0, v - 1)
            mb_c = jnp.clip(mb, 0, m - 1)
            slot_c = jnp.clip(slot, 0, ring - 1)
            stage_s = chunk_c * p + rank  # logical stage of this op
            sp_c = jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, chunk_c, keepdims=False
                ),
                sp,
            )
            gst_c = {
                k: jax.lax.dynamic_index_in_dim(gv, chunk_c, keepdims=False)
                for k, gv in gst.items()
            }

            def idle_branch(carry):
                return carry, zero_msg, zero_meta, zero_msg, zero_meta

            def f_branch(carry):
                feed = jax.lax.dynamic_index_in_dim(
                    x_feed, mb_c, keepdims=False
                )
                inbox = jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(
                        carry['act_in'], chunk_c, keepdims=False
                    ),
                    mb_c % d_act, keepdims=False,
                )
                x_in = jnp.where(stage_s == 0, feed, inbox)
                # the last logical stage's output is consumed by ITS OWN
                # backward (head+loss recompute under vjp), never sent —
                # skip the forward entirely there instead of computing a
                # discarded y
                y = jax.lax.cond(
                    stage_s < last_stage,
                    lambda x: self.stage.apply({'params': sp_c}, x).astype(
                        self.dtype
                    ),
                    # fresh zeros are vma-unvarying; match the true branch
                    lambda x: jax.lax.pcast(
                        jnp.zeros(x.shape, self.dtype), all_axes,
                        to='varying',
                    ),
                    x_in,
                )
                new = dict(carry)
                new['resid'] = jax.lax.dynamic_update_index_in_dim(
                    carry['resid'], x_in, slot_c, 0
                )
                send_valid = (stage_s < last_stage).astype(jnp.int32)
                nxt = stage_s + 1
                meta = jnp.stack(
                    [nxt // p, mb_c, send_valid]
                ).astype(jnp.int32)
                return (
                    new, y.astype(self.dtype) * send_valid.astype(y.dtype),
                    meta, zero_msg, zero_meta,
                )

            def b_branch(carry):
                x_saved = jax.lax.dynamic_index_in_dim(
                    carry['resid'], slot_c, keepdims=False
                )
                ybar_ext = jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(
                        carry['cot_in'], chunk_c, keepdims=False
                    ),
                    mb_c % d_cot, keepdims=False,
                )
                is_last = stage_s == last_stage
                tgt = jax.lax.dynamic_index_in_dim(
                    t_feed, mb_c, keepdims=False
                )

                def primal(sp_, x_, gst_, hp, lp):
                    y, tick_a = self._stage_apply_captured(
                        sp_, gst_, x_, jnp.float32(1.0)
                    )
                    lval = jax.lax.cond(
                        is_last,
                        lambda: head_loss(y, hp, lp, tgt),
                        lambda: jax.lax.pcast(
                            jnp.zeros((), jnp.float32), all_axes,
                            to='varying',
                        ),
                    )
                    return (y, lval), tick_a

                (_, lval), pull, tick_a = jax.vjp(
                    primal, sp_c, x_saved, gst_c, head_params, lnf_params,
                    has_aux=True,
                )
                ybar = jnp.where(
                    is_last, jnp.zeros_like(ybar_ext), ybar_ext
                ).astype(self.dtype)
                spbar, xbar_x, gdbar, hbar, lbar = pull(
                    (
                        ybar,
                        jax.lax.pcast(
                            jnp.ones((), jnp.float32), all_axes,
                            to='varying',
                        ),
                    )
                )
                new = dict(carry)
                new['loss'] = carry['loss'] + lval
                new['sgrads'] = jax.tree_util.tree_map(
                    lambda acc, g: acc.at[chunk_c].add(g),
                    carry['sgrads'], spbar,
                )
                new['hgrads'] = jax.tree_util.tree_map(
                    lambda acc, g: acc + g, carry['hgrads'], hbar
                )
                new['lgrads'] = jax.tree_util.tree_map(
                    lambda acc, g: acc + g, carry['lgrads'], lbar
                )
                new['a_acc'] = {
                    k: carry['a_acc'][k].at[chunk_c].add(tick_a[k])
                    for k in tick_a
                }
                new['g_acc'] = {
                    k: carry['g_acc'][k].at[chunk_c].add(gdbar[k])
                    for k in gdbar
                }
                new['n_b'] = carry['n_b'].at[chunk_c].add(1.0)
                xbar_x = xbar_x.astype(self.dtype)
                new['xbar'] = jax.lax.dynamic_update_index_in_dim(
                    carry['xbar'],
                    jnp.where(
                        stage_s == 0,
                        xbar_x,
                        jax.lax.dynamic_index_in_dim(
                            carry['xbar'], mb_c, keepdims=False
                        ),
                    ),
                    mb_c, 0,
                )
                send_valid = (stage_s > 0).astype(jnp.int32)
                prev = jnp.maximum(stage_s - 1, 0)
                meta = jnp.stack(
                    [prev // p, mb_c, send_valid]
                ).astype(jnp.int32)
                return (
                    new, zero_msg, zero_meta,
                    xbar_x * send_valid.astype(xbar_x.dtype), meta,
                )

            carry, s_act, am, s_cot, cm = jax.lax.switch(
                kind + 1, [idle_branch, f_branch, b_branch], carry
            )
            carry['ticks'] = carry['ticks'] + jnp.stack(
                [kind == 0, kind == 1, kind < 0]
            ).astype(jnp.int32)

            # uniform collectives: every rank permutes every tick (invalid
            # messages are zeros; the metadata valid flag gates the write)
            r_act = jax.lax.ppermute(s_act, PIPE_AXIS, fwd_perm)
            r_am = jax.lax.ppermute(am, PIPE_AXIS, fwd_perm)
            r_cot = jax.lax.ppermute(s_cot, PIPE_AXIS, bwd_perm)
            r_cm = jax.lax.ppermute(cm, PIPE_AXIS, bwd_perm)

            def deliver(inbox, msg, meta, depth):
                c_i = jnp.clip(meta[0], 0, v - 1)
                s_i = jnp.clip(meta[1], 0, m - 1) % depth
                cur = jax.lax.dynamic_index_in_dim(
                    jax.lax.dynamic_index_in_dim(inbox, c_i, keepdims=False),
                    s_i, keepdims=False,
                )
                val = jnp.where(meta[2] > 0, msg, cur)
                row = jax.lax.dynamic_update_index_in_dim(
                    jax.lax.dynamic_index_in_dim(inbox, c_i, keepdims=False),
                    val, s_i, 0,
                )
                return jax.lax.dynamic_update_index_in_dim(
                    inbox, row, c_i, 0
                )

            carry['act_in'] = deliver(carry['act_in'], r_act, r_am, d_act)
            carry['cot_in'] = deliver(carry['cot_in'], r_cot, r_cm, d_cot)
            return carry, None

        carry, _ = jax.lax.scan(tick, carry0, ops_r)

        loss_sum = jax.lax.psum(carry['loss'], all_axes)
        sgrads = carry['sgrads']
        hgrads = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, all_axes), carry['hgrads']
        )
        lgrads = jax.tree_util.tree_map(
            lambda x: jax.lax.psum(x, all_axes), carry['lgrads']
        )
        a_acc, g_acc, n_b = carry['a_acc'], carry['g_acc'], carry['n_b']
        if self.data_axes:
            sgrads = jax.tree_util.tree_map(
                lambda x: jax.lax.psum(x, self.data_axes), sgrads
            )
            a_acc = {
                k: jax.lax.psum(x, self.data_axes) for k, x in a_acc.items()
            }
            g_acc = {
                k: jax.lax.psum(x, self.data_axes) for k, x in g_acc.items()
            }
            n_b = jax.lax.psum(n_b, self.data_axes)
        xbar = jax.lax.psum(carry['xbar'], PIPE_AXIS)
        tick_counts = carry['ticks']
        if self.data_axes:
            # every dp replica of a pipe rank counted the same schedule;
            # pmax collapses the data axes without inflating the counts
            tick_counts = jax.lax.pmax(tick_counts, self.data_axes)
        return (
            loss_sum, sgrads, hgrads, lgrads, a_acc, g_acc, n_b, xbar,
            tick_counts[None],
        )

    # ------------------------------------------------------------- loss

    def loss_and_stats(self, params, batch):
        """(loss, grads, chunk-stacked stats) from the single-slot scan."""
        loss, grads, stats, _ = self.loss_stats_and_ticks(params, batch)
        return loss, grads, stats

    def loss_stats_and_ticks(self, params, batch):
        """:meth:`loss_and_stats` plus the per-rank ``(p, 3)`` int32
        tick counters ``(executed F, executed B, idle)`` surfaced from
        the scan carry — the runtime ground truth
        :meth:`tick_report` diffs against the schedule tables."""
        tokens, targets = batch
        b, s = tokens.shape
        m = self.n_microbatches
        self._validate_batch(b)
        if m % self.p_ranks != 0:
            raise ValueError(
                f'n_microbatches ({m}) must be a multiple of the pipeline '
                f'rank count ({self.p_ranks}) for interleaving'
            )
        gstats0 = self.zero_gstats()

        def embed_fn(ep):
            x = self._embed({'embed': ep['embed'],
                             'pos_embed': ep['pos_embed']}, tokens)
            return x.reshape(m, b // m, s, self.d_model)

        epar = {'embed': params['embed'], 'pos_embed': params['pos_embed']}
        x_feed, embed_pull = jax.vjp(embed_fn, epar)
        t_feed = targets.reshape(m, b // m, s)

        gspec = {k: P(PIPE_AXIS) for k in gstats0}
        bspec = P(None, self.data_axes) if self.data_axes else P()
        out = jax.shard_map(
            self._body_interleaved,
            mesh=self.mesh,
            axis_names=self._manual,
            in_specs=(P(PIPE_AXIS), P(), P(), bspec, bspec, gspec),
            out_specs=(
                P(),
                jax.tree_util.tree_map(lambda _: P(PIPE_AXIS),
                                       params['stages']),
                P(),
                P(),
                {k: P(PIPE_AXIS) for k in gstats0},
                {k: P(PIPE_AXIS) for k in gstats0},
                P(PIPE_AXIS),
                bspec,
                P(PIPE_AXIS),
            ),
        )(params['stages'], params['head'], params['ln_f'], x_feed, t_feed,
          gstats0)
        (loss, sgrads, hgrads, lgrads, a_stats, g_stats, counts, xbar,
         tick_counts) = out
        (egrads,) = embed_pull(xbar)
        grads = {
            'embed': egrads['embed'],
            'pos_embed': egrads['pos_embed'],
            'stages': sgrads,
            'head': hgrads,
            'ln_f': lgrads,
        }
        denom = jnp.maximum(counts, 1.0)
        a_avg = {k: x / denom[:, None, None] for k, x in a_stats.items()}
        g_avg = {k: x / denom[:, None, None] for k, x in g_stats.items()}
        return (
            loss, grads, capture_lib.CapturedStats(a=a_avg, g=g_avg),
            tick_counts,
        )

    # ------------------------------------------------------------ report

    def tick_report(self, tick_counts=None):
        """``comms_report()``-style schedule accounting for this model.

        The ``predicted`` block comes from the static schedule tables
        (exact per-rank F/B/idle slot counts and the simulator's
        :meth:`~kfac_tpu.parallel.interleaved.SingleSlotSchedule.bubble_slots`);
        pass the counters returned by :meth:`loss_stats_and_ticks` as
        ``tick_counts`` to fold in the EXECUTED counts and the
        ``matches_schedule`` verdict.
        """
        import numpy as np

        sched = self._sched
        kinds = np.asarray(sched.ops)[:, :, 0]
        predicted = np.stack(
            [(kinds == 0).sum(0), (kinds == 1).sum(0), (kinds < 0).sum(0)],
            axis=1,
        )
        p = self.p_ranks
        out = {
            'schedule': self.schedule,
            'p_ranks': p,
            'virtual_chunks': self.virtual_chunks,
            'n_microbatches': self.n_microbatches,
            'ticks': int(sched.ticks),
            'bubble_slots': int(sched.bubble_slots()),
            'bubble_fraction': float(sched.bubble_slots())
            / float(sched.ticks * p),
            'predicted': {
                'executed_f': predicted[:, 0].tolist(),
                'executed_b': predicted[:, 1].tolist(),
                'idle': predicted[:, 2].tolist(),
            },
        }
        if tick_counts is not None:
            executed = np.asarray(tick_counts)
            out['executed'] = {
                'executed_f': executed[:, 0].tolist(),
                'executed_b': executed[:, 1].tolist(),
                'idle': executed[:, 2].tolist(),
            }
            out['matches_schedule'] = bool((executed == predicted).all())
        return out
