"""Pipeline parallelism (GPipe schedule) with K-FAC, SPMD-style.

Capability parity with the reference's GPT-NeoX pipeline support
(kfac/gpt_neox/: DeepSpeed PipelineModule topology, factors assigned among
pipe-parallel peers, hardwired MEM-OPT — gpt_neox/assignment.py:95-130),
re-designed for a TPU mesh:

- Stage parameters are STACKED on a leading stage axis and sharded over the
  ``pipe`` mesh axis; every device runs the same traced program on its
  stage slice (no per-rank module partitioning).
- The schedule is a ``lax.scan`` over ticks: each tick applies the local
  stage to the activation in flight and ``ppermute``s it to the next stage.
  Microbatches enter at stage 0 and exit at the last stage
  (fill/drain bubbles compute on zeros and are masked out of statistics and
  outputs).
- K-FAC curvature capture cannot use the global interceptor-closure trick
  here (stats live inside the shard_map/scan trace), so the pipeline body
  accumulates A statistics in the scan carry and routes G statistics out
  through custom_vjp g-taps whose dummies are shard_map arguments with a
  stage-sharded leading axis.
- Second-order state for stage layers keeps that stage axis and stays
  sharded over ``pipe``: each stage eigendecomposes and preconditions only
  its own layers — the reference's MEM-OPT-among-pipe-peers placement,
  with zero inverse traffic across stages.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kfac_tpu.layers import capture as capture_lib
from kfac_tpu.layers import registry as registry_lib
from kfac_tpu.models import transformer as transformer_lib
from kfac_tpu.ops import factors as factors_lib
from kfac_tpu.ops import losses as losses_lib
from kfac_tpu.parallel import mesh as mesh_lib
from kfac_tpu.preconditioner import KFACPreconditioner, _resolve

PIPE_AXIS = mesh_lib.PIPE_AXIS


class StageBlocks(nn.Module):
    """A pipeline stage: ``blocks_per_stage`` transformer blocks."""

    blocks_per_stage: int
    num_heads: int
    mlp_ratio: int = 4
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        for i in range(self.blocks_per_stage):
            x = transformer_lib.Block(
                self.num_heads, self.mlp_ratio, dtype=self.dtype,
                name=f'block{i}',
            )(x)
        return x


@dataclasses.dataclass
class PipelinedLM:
    """Decoder LM with its blocks pipelined over a ``pipe`` mesh axis.

    Embedding and the output head run replicated outside the pipeline (they
    are a small fraction of compute); the block stack runs under the GPipe
    schedule. ``n_microbatches`` must divide the batch.

    The per-stage module defaults to :class:`StageBlocks` (transformer
    blocks) but ANY flax module mapping ``(B, S, d_model) -> (B, S,
    d_model)`` can be pipelined via ``stage_module`` — the counterpart of
    the reference wrapping arbitrary DeepSpeed ``PipelineModule``s
    (kfac/gpt_neox/preconditioner.py:161-165). The K-FAC registry, capture
    taps, TP sharding rules, and both schedules are derived from the module
    itself, so no other knob changes.
    """

    mesh: Mesh
    vocab_size: int
    d_model: int
    num_heads: int
    num_layers: int
    n_microbatches: int = 4
    mlp_ratio: int = 4
    max_len: int = 2048
    dtype: Any = jnp.float32
    # Rematerialize each stage application in the backward pass: residual
    # memory drops from every internal activation of every tick to just the
    # per-tick stage inputs — the memory profile 1F1B buys over GPipe,
    # traded for ~1/3 extra stage FLOPs instead of a hand-scheduled
    # backward (XLA recomputes inside the scan's transpose).
    remat: bool = True
    # 'gpipe': forward scan + autodiff transpose (residual memory grows
    # with n_microbatches: the scan saves one stage input per tick).
    # '1f1b': ONE combined scan computes forward and backward slots per
    # tick — stage s runs F of microbatch (t - s) and B of microbatch
    # (t - (2S-2-s)); the last stage computes head+loss+cotangent in-tick
    # so backward drains while the pipe is still filling. STAGE residual
    # memory is a (2S-1)-slot ring regardless of n_microbatches — the 1F1B
    # memory bound (vs DeepSpeed's PipelineEngine schedule the reference
    # rides, kfac/gpt_neox/preconditioner.py:70-73); the O(M) buffers that
    # remain are the model's own input feed and the stage-0 input-cotangent
    # collection for the embed backward (GPipe carries both too, PLUS one
    # saved stage input per tick). The bubble fraction (2S-2)/(M+2S-2) can
    # therefore be amortized with as many microbatches as the batch
    # affords. Loss, parameter grads, AND the K-FAC A/G statistics come
    # out of the same scan: B slots recompute the stage forward under an
    # explicit jax.vjp with the capture interceptor + g-taps attached.
    schedule: str = 'gpipe'
    # regex patterns excluding stage layers from K-FAC registration (same
    # semantics as register_model's skip_layers; the reference's LM example
    # skips attention projections this way)
    skip_layers: tuple[str, ...] | None = None
    # Tensor-parallel kinds for stage layers (layer-name regex -> 'column' /
    # 'row' / 'replicated'), used when the mesh has a model axis of size >1.
    # Defaults cover StageBlocks' Megatron pairing: qkv/mlp_up
    # column-parallel, out_proj/mlp_down row-parallel — the reference's
    # ColumnParallelLinear/RowParallelLinear assignment
    # (kfac/gpt_neox/preconditioner.py:189-191).
    tp_overrides: tuple[tuple[str, str], ...] = (
        (r'.*(q_proj|k_proj|v_proj|mlp_up)', 'column'),
        (r'.*(out_proj|mlp_down)', 'row'),
    )
    # Custom per-stage module: any flax module (B, S, d_model) ->
    # (B, S, d_model). None selects StageBlocks(num_layers / n_stages
    # transformer blocks). With a custom module, num_layers/mlp_ratio are
    # ignored for stage construction (num_heads only feeds StageBlocks).
    stage_module: nn.Module | None = None

    def __post_init__(self) -> None:
        import warnings as _warnings

        from kfac_tpu.warnings import ExperimentalFeatureWarning

        _warnings.warn(
            'pipeline-parallel K-FAC is experimental (the reference flags '
            'its pipeline support the same way)',
            ExperimentalFeatureWarning,
            stacklevel=2,
        )
        if self.schedule not in ('gpipe', '1f1b', 'interleaved'):
            raise ValueError(
                f"unknown schedule {self.schedule!r}: 'gpipe', '1f1b', or "
                f"'interleaved'"
            )
        if self.schedule == 'interleaved' and not self._executes_interleaved():
            raise ValueError(
                "the 'interleaved' schedule requires "
                'InterleavedPipelinedLM (parallel/interleaved_scan.py)'
            )
        # logical stage count: pipe ranks x chunks per rank (1 for this
        # class; InterleavedPipelinedLM overrides _chunks_per_rank so the
        # stage module/registry below are built ONCE with the right count)
        self.n_stages = int(self.mesh.shape[PIPE_AXIS]) * (
            self._chunks_per_rank()
        )
        # Every non-pipe, non-model mesh axis is a data-parallel axis: the
        # batch shards over them and factor statistics reduce over them (the
        # reference's factor allreduce over the DP group,
        # kfac/gpt_neox/layer.py:61-93). The model axis (TP) is NOT a data
        # axis: the schedule leaves it automatic — shard_map runs manual
        # over pipe+data only — so GSPMD inserts the Megatron all-reduces
        # inside each stage application (the reference's 3D composition,
        # kfac/gpt_neox/preconditioner.py:70-73,189-191).
        self.data_axes = tuple(
            ax
            for ax in self.mesh.axis_names
            if ax not in (PIPE_AXIS, mesh_lib.MODEL_AXIS)
        )
        self.tp = int(dict(self.mesh.shape).get(mesh_lib.MODEL_AXIS, 1))
        self._manual = frozenset((PIPE_AXIS,) + self.data_axes)
        self.embed = nn.Embed(self.vocab_size, self.d_model, name='embed')
        if self.stage_module is not None:
            self.stage = self.stage_module
        else:
            if self.num_layers % self.n_stages != 0:
                raise ValueError('num_layers must divide evenly into stages')
            self.blocks_per_stage = self.num_layers // self.n_stages
            self.stage = StageBlocks(
                self.blocks_per_stage, self.num_heads, self.mlp_ratio,
                self.dtype,
            )
        self.head = nn.Dense(self.vocab_size, use_bias=False, name='lm_head')
        self.ln_f = nn.LayerNorm(dtype=jnp.float32, name='ln_f')
        # Registry of one stage's K-FAC layers (shapes identical per stage).
        x = jnp.zeros((1, 8, self.d_model), self.dtype)
        out_shape = jax.eval_shape(
            lambda v: self.stage.init_with_output(
                jax.random.PRNGKey(0), v
            )[0],
            x,
        ).shape
        if out_shape != x.shape:
            raise ValueError(
                f'stage module must map (B, S, {self.d_model}) to itself '
                f'(pipeline stages chain), got output shape {out_shape}'
            )
        self.stage_registry = registry_lib.register_model(
            self.stage, x, skip_layers=list(self.skip_layers or []),
        )
        # the in-schedule capture averages by invocation count with no
        # weights path; a weighted (routed) helper would come out of
        # g_factor_for_sum pre-scaled by its live fraction and silently
        # mis-scale G vs A — reject rather than mis-precondition
        weighted = [
            n for n, h in self.stage_registry.layers.items()
            if getattr(h, 'weighted', False)
        ]
        if weighted:
            raise NotImplementedError(
                f'routed (traffic-weighted) layers {weighted} are not '
                'supported inside pipeline stages; the pipeline capture '
                'keeps equal-weight averaging (see '
                'cov.routed_linear_a_factor exactness notes)'
            )
        self._gtaps = {
            name: capture_lib._make_gtap(h)
            for name, h in self.stage_registry.layers.items()
        }

    def _chunks_per_rank(self) -> int:
        """Model chunks per pipeline rank (1 here; the interleaved
        subclass returns ``virtual_chunks``)."""
        return 1

    def _executes_interleaved(self) -> bool:
        """Whether this class runs the single-slot interleaved scan —
        NOT the same as ``_chunks_per_rank() > 1``: an
        InterleavedPipelinedLM with ``virtual_chunks=1`` is valid and
        still executes the interleaved scan."""
        return False

    def _make_head_loss(self, total_tokens: float):
        """Summed-token-NLL/total_tokens closure shared by the combined
        1F1B and single-slot interleaved bodies (the fused NLL keeps the
        head vocab-parallel when the kernel is sharded over the automatic
        model axis — ops/losses.vocab_parallel_nll)."""

        def head_loss(y, hp, lp, tgt):
            yl = self.ln_f.apply({'params': lp}, y.astype(jnp.float32))
            logits = self.head.apply({'params': hp}, yl)
            return jnp.sum(losses_lib.vocab_parallel_nll(logits, tgt)) / (
                total_tokens
            )

        return head_loss

    @staticmethod
    def _zeros_like_vary(all_axes):
        """Fresh zeros pcast varying over ``all_axes`` (scan carries and
        cond branches must agree with the inputs' vma types)."""
        return lambda t: jax.tree_util.tree_map(
            lambda v: jax.lax.pcast(
                jnp.zeros(v.shape, v.dtype), all_axes, to='varying'
            ),
            t,
        )

    # ------------------------------------------------------------ params

    def init(self, rng: jax.Array) -> dict[str, Any]:
        r_embed, r_stage, r_head, r_pos = jax.random.split(rng, 4)
        dummy_tok = jnp.zeros((1, 8), jnp.int32)
        dummy_x = jnp.zeros((1, 8, self.d_model), self.dtype)
        stage_rngs = jax.random.split(r_stage, self.n_stages)
        stage_params = jax.vmap(
            lambda r: self.stage.init(r, dummy_x)['params']
        )(stage_rngs)
        params = {
            'embed': self.embed.init(r_embed, dummy_tok)['params'],
            'pos_embed': jax.random.normal(
                r_pos, (self.max_len, self.d_model)
            ) * 0.02,
            'stages': stage_params,  # every leaf has leading dim n_stages
            'ln_f': self.ln_f.init(
                jax.random.PRNGKey(0), dummy_x.astype(jnp.float32)
            )['params'],
            'head': self.head.init(r_head, dummy_x.astype(jnp.float32))['params'],
        }
        # place stage params sharded over the pipe axis; with TP active the
        # feature dims additionally shard over the model axis per the
        # registry-derived Megatron kinds
        if self.tp > 1:
            from kfac_tpu.parallel import tensor_parallel

            tp_specs = tensor_parallel.registry_param_specs(
                params['stages'],
                self.stage_registry,
                overrides=self.tp_overrides,
                warn_unmatched=False,
            )
            if not any(
                mesh_lib.MODEL_AXIS in s
                for s in jax.tree_util.tree_leaves(
                    tp_specs, is_leaf=lambda x: isinstance(x, P)
                )
            ):
                import warnings as _warnings

                _warnings.warn(
                    f'model axis has {self.tp} shards but NO stage '
                    'parameter matched a tensor-parallel rule — all stage '
                    'weights are fully replicated over the model axis. '
                    'Pass tp_overrides mapping your stage layer names to '
                    "'column'/'row' (square layers are never sharded by "
                    'the shape heuristic).',
                    tensor_parallel.UnshardedParamWarning,
                    stacklevel=2,
                )
            params['stages'] = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(
                    x, NamedSharding(self.mesh, P(PIPE_AXIS, *s))
                ),
                params['stages'],
                tp_specs,
            )
            # Vocab-parallel LM head (Megatron's VocabParallelEmbedding
            # pairing, which the reference rides through its GPT-NeoX
            # integration): the (d, V) kernel shards V over the model axis
            # per the SAME rule table the dense TransformerLM uses
            # (TRANSFORMER_TP_RULES '.*lm_head/kernel'), so the two paths
            # cannot drift apart. The model axis is automatic in both
            # schedules' shard_maps, so GSPMD keeps the head matmul and the
            # fused NLL's softmax reductions (ops/losses.vocab_parallel_nll)
            # at 1/tp per device instead of replicating the full d x V
            # matmul per microbatch.
            params['head'] = tensor_parallel.shard_params(
                {'lm_head': params['head']}, self.mesh
            )['lm_head']
        else:
            stage_sharding = NamedSharding(self.mesh, P(PIPE_AXIS))
            params['stages'] = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, stage_sharding), params['stages']
            )
        return params

    # ----------------------------------------------------------- pipeline

    def _stage_apply_captured(self, sp, gst, x, valid):
        """One stage application with curvature taps attached.

        Returns ``(y, tick_a)``: the stage output with g-taps wrapped
        around every registered layer (their vjp emits G factors into the
        ``gst`` dummies' cotangents) and the per-layer A factors of this
        application, masked by ``valid``. Shared by the GPipe forward body
        and the 1F1B backward-slot recompute so capture semantics cannot
        diverge between schedules.
        """
        registry = self.stage_registry
        tick_a: dict[str, jax.Array] = {}

        def interceptor(next_fun, iargs, ikwargs, context):
            mod = context.module
            if context.method_name != '__call__' or not iargs:
                return next_fun(*iargs, **ikwargs)
            name = registry_lib.path_name(mod.path)
            helper = registry.layers.get(name)
            if helper is None:
                return next_fun(*iargs, **ikwargs)
            a = capture_lib.layer_input(mod, iargs[0])
            tick_a[name] = tick_a.get(name, 0.0) + (
                helper.get_a_factor(a) * valid
            )
            y = next_fun(*iargs, **ikwargs)
            # masked ticks contribute zero: their outputs never reach the
            # loss, so cotangents — and G contributions — are exactly zero
            return self._gtaps[name](y, gst[name])

        with nn.intercept_methods(interceptor):
            y = self.stage.apply({'params': sp}, x)
        return y, tick_a

    def _validate_batch(self, b: int) -> int:
        """Check batch divisibility; returns the data-parallel world."""
        m = self.n_microbatches
        if b % m != 0:
            raise ValueError(f'batch {b} not divisible by {m} microbatches')
        dp = 1
        for ax in self.data_axes:
            dp *= int(self.mesh.shape[ax])
        if (b // m) % dp != 0:
            raise ValueError(
                f'per-microbatch batch {b // m} not divisible by the '
                f'data-parallel world {dp}'
            )
        return dp

    def _pipeline_body(self, stage_params, x_feed, gstats):
        """shard_map body: local stage over all ticks of the schedule.

        Args (local views):
            stage_params: this stage's params (leading dim 1).
            x_feed: (M, B_m, S, D) microbatch activations (replicated).
            gstats: zero g-tap dummies, leading dim 1 (this stage's slice).
        Returns (local views):
            out: (M, B_m, S, D) last-stage outputs (valid on last stage).
            a_stats: dict name -> (1, da, da) summed A statistics.
            counts: (1,) number of real microbatches processed.
        """
        sp = jax.tree_util.tree_map(lambda x: x[0], stage_params)
        gst = {k: v[0] for k, v in gstats.items()}
        if self.data_axes:
            # Stage params/g-dummies are replicated over the data axes and
            # the batch feed over pipe; broadcast all to the full varying
            # set so the schedule mixes them freely. The pcast over the data
            # axes transposes to a psum — exactly the DP reduction for
            # stage gradients and G statistics.
            sp = jax.tree_util.tree_map(
                lambda v: jax.lax.pcast(v, self.data_axes, to='varying'), sp
            )
            gst = {
                k: jax.lax.pcast(v, self.data_axes, to='varying')
                for k, v in gst.items()
            }
            x_feed = jax.lax.pcast(x_feed, (PIPE_AXIS,), to='varying')
        stage_idx = jax.lax.axis_index(PIPE_AXIS)
        if self.data_axes:
            stage_idx = jax.lax.pcast(
                stage_idx, self.data_axes, to='varying'
            )
        n = self.n_stages
        m = self.n_microbatches
        ticks = m + n - 1
        b_m, s, d = x_feed.shape[1:]
        perm = [(j, (j + 1) % n) for j in range(n)]
        registry = self.stage_registry

        def apply_stage(x, valid):
            return self._stage_apply_captured(sp, gst, x, valid)

        if self.remat:
            apply_stage = jax.checkpoint(apply_stage)

        zero_a = {
            name: jnp.zeros(h.a_factor_shape, jnp.float32)
            for name, h in registry.layers.items()
        }

        def tick(carry, t):
            x_in, a_acc, n_valid = carry
            # stage 0 ingests microbatch t (zeros once the feed is drained)
            feed_mask = (t < m).astype(x_feed.dtype)
            feed = feed_mask * jax.lax.dynamic_index_in_dim(
                x_feed, jnp.minimum(t, m - 1), keepdims=False
            )
            x_in = jnp.where(stage_idx == 0, feed, x_in)
            # my microbatch index at this tick; valid while in [0, m)
            mb = t - stage_idx
            valid = jnp.logical_and(mb >= 0, mb < m)
            validf = valid.astype(jnp.float32)
            y, tick_a = apply_stage(x_in, validf)
            a_acc = {k: a_acc[k] + tick_a[k] for k in a_acc}
            n_valid = n_valid + validf
            # keep only real outputs; bubbles propagate zeros
            y = y * validf.astype(y.dtype)
            x_next = jax.lax.ppermute(y, PIPE_AXIS, perm)
            return (x_next, a_acc, n_valid), (y, mb)

        all_axes = (PIPE_AXIS,) + self.data_axes
        x0 = jax.lax.pcast(
            jnp.zeros((b_m, s, d), self.dtype), all_axes, to='varying'
        )
        zero_a = jax.tree_util.tree_map(
            lambda v: jax.lax.pcast(v, all_axes, to='varying'), zero_a
        )
        n_valid0 = jax.lax.pcast(
            jnp.zeros((), jnp.float32), all_axes, to='varying'
        )
        (x_last, a_acc, n_valid), (ys, mbs) = jax.lax.scan(
            tick, (x0, zero_a, n_valid0), jnp.arange(ticks)
        )
        # gather this stage's outputs into microbatch order (only the last
        # stage's are real; others zero)
        out = jax.lax.pcast(
            jnp.zeros((m, b_m, s, d), self.dtype), all_axes, to='varying'
        )
        is_last = (stage_idx == n - 1).astype(self.dtype)

        def collect(out, ty):
            t, y, mb = ty
            mb_c = jnp.clip(mb, 0, m - 1)
            cur = jax.lax.dynamic_index_in_dim(out, mb_c, keepdims=False)
            upd = jnp.where((mb >= 0) & (mb < m), y * is_last, cur)
            return jax.lax.dynamic_update_index_in_dim(out, upd, mb_c, 0), None

        out, _ = jax.lax.scan(
            collect, out, (jnp.arange(ticks), ys, mbs)
        )
        # only the last stage holds real outputs (zeros elsewhere): the psum
        # is the broadcast from the final stage to the world
        out = jax.lax.psum(out, PIPE_AXIS)
        if self.data_axes:
            # DP factor reduction: sum A stats and tick counts over the data
            # axes; loss_and_stats divides by the summed counts, yielding
            # the global-batch mean (per-tick factors normalize by local
            # rows, so the division is exact for any dp size).
            a_acc = {
                k: jax.lax.psum(v, self.data_axes) for k, v in a_acc.items()
            }
            n_valid = jax.lax.psum(n_valid, self.data_axes)
        a_stats = {k: v[None] for k, v in a_acc.items()}
        return out, a_stats, n_valid[None]

    def _embed(self, params, tokens):
        x = self.embed.apply({'params': params['embed']}, tokens)
        pos = params['pos_embed'][: tokens.shape[-1]]
        return (x + pos).astype(self.dtype)

    def zero_gstats(self):
        return {
            name: jnp.zeros((self.n_stages,) + h.g_factor_shape, jnp.float32)
            for name, h in self.stage_registry.layers.items()
        }

    def apply(self, params, tokens, gstats=None):
        """Pipelined forward: tokens (B, S) -> logits (B, S, V).

        Returns (logits, a_stats, counts); ``a_stats`` have a leading
        stage axis sharded over ``pipe``.
        """
        if gstats is None:
            gstats = self.zero_gstats()
        b, s = tokens.shape
        m = self.n_microbatches
        self._validate_batch(b)
        x = self._embed(params, tokens)
        x_feed = x.reshape(m, b // m, s, self.d_model)

        gspec = {k: P(PIPE_AXIS) for k in gstats}
        # (M, B_m, S, D) feed/output: the per-microbatch batch dim shards
        # over the data axes; each data peer pipelines its own batch shard.
        bspec = P(None, self.data_axes) if self.data_axes else P()
        out, a_stats, counts = jax.shard_map(
            self._pipeline_body,
            mesh=self.mesh,
            in_specs=(P(PIPE_AXIS), bspec, gspec),
            out_specs=(bspec, {k: P(PIPE_AXIS) for k in gstats}, P(PIPE_AXIS)),
            axis_names=self._manual,  # model stays automatic (TP via GSPMD)
        )(params['stages'], x_feed, gstats)
        x = out.reshape(b, s, self.d_model)
        x = self.ln_f.apply({'params': params['ln_f']}, x.astype(jnp.float32))
        logits = self.head.apply({'params': params['head']}, x)
        return logits, a_stats, counts

    # ------------------------------------------------------------- 1f1b

    def _body_1f1b(
        self, stage_params, head_params, lnf_params, x_feed, t_feed, gstats
    ):
        """shard_map body: the combined F/B schedule over all ticks.

        Args (local views):
            stage_params: this stage's params (leading dim 1).
            head_params / lnf_params: head + final-norm params — replicated
                over the manual (pipe/data) axes; with tp > 1 the head
                kernel is vocab-sharded over the AUTOMATIC model axis, so
                head logic in this body must stay GSPMD-partitionable (no
                ops assuming a full local vocab copy).
            x_feed: (M, B_m, S, D) microbatch activations.
            t_feed: (M, B_m, S) target ids.
            gstats: zero g-tap dummies, leading dim 1 (this stage's slice).
        Returns (local views):
            loss_sum: () local sum of token NLLs / total_tokens.
            stage_grads: this stage's param grads (leading dim 1).
            head_grads / lnf_grads: zero except on the last stage.
            a_stats / g_stats: dict name -> (1, d, d) summed statistics.
            counts: (1,) microbatches processed by this stage's B slots.
            xbar: (M, B_m, S, D) input cotangents (real on stage 0 only).
        """
        sp = jax.tree_util.tree_map(lambda x: x[0], stage_params)
        gst = {k: v[0] for k, v in gstats.items()}
        n = self.n_stages
        m = self.n_microbatches
        registry = self.stage_registry
        all_axes = (PIPE_AXIS,) + self.data_axes
        if self.data_axes:
            vary = lambda t: jax.tree_util.tree_map(
                lambda v: jax.lax.pcast(v, self.data_axes, to='varying'), t
            )
            sp, gst = vary(sp), vary(gst)
            x_feed = jax.lax.pcast(x_feed, (PIPE_AXIS,), to='varying')
            t_feed = jax.lax.pcast(t_feed, (PIPE_AXIS,), to='varying')
        # head/ln_f arrive fully replicated (P()): vary over every axis so
        # the cond branches and accumulators agree
        head_params, lnf_params = jax.tree_util.tree_map(
            lambda v: jax.lax.pcast(v, all_axes, to='varying'),
            (head_params, lnf_params),
        )
        stage_idx = jax.lax.axis_index(PIPE_AXIS)
        if self.data_axes:
            stage_idx = jax.lax.pcast(stage_idx, self.data_axes, to='varying')
        b_m, s_len, d = x_feed.shape[1:]
        ticks = m + 2 * n - 2
        ring = 2 * n - 1
        dp = 1
        for ax in self.data_axes:
            dp *= int(self.mesh.shape[ax])
        total_tokens = float(m * b_m * s_len * dp)
        fwd_perm = [(j, (j + 1) % n) for j in range(n)]
        bwd_perm = [(j, (j - 1) % n) for j in range(n)]

        head_loss = self._make_head_loss(total_tokens)
        zero_a = {
            name: jnp.zeros(h.a_factor_shape, jnp.float32)
            for name, h in registry.layers.items()
        }
        zeros_like_vary = self._zeros_like_vary(all_axes)

        carry0 = dict(
            x_f=zeros_like_vary(jnp.zeros((b_m, s_len, d), self.dtype)),
            g_b=zeros_like_vary(jnp.zeros((b_m, s_len, d), self.dtype)),
            resid=zeros_like_vary(jnp.zeros((ring, b_m, s_len, d), self.dtype)),
            xbar=zeros_like_vary(jnp.zeros((m, b_m, s_len, d), self.dtype)),
            loss=zeros_like_vary(jnp.zeros((), jnp.float32)),
            sgrads=zeros_like_vary(
                jax.tree_util.tree_map(jnp.zeros_like, sp)
            ),
            hgrads=zeros_like_vary(
                jax.tree_util.tree_map(
                    lambda v: jnp.zeros_like(v, jnp.float32), head_params
                )
            ),
            lgrads=zeros_like_vary(
                jax.tree_util.tree_map(
                    lambda v: jnp.zeros_like(v, jnp.float32), lnf_params
                )
            ),
            a_acc=zeros_like_vary(zero_a),
            g_acc=zeros_like_vary(
                {k: jnp.zeros_like(v) for k, v in gst.items()}
            ),
            n_b=zeros_like_vary(jnp.zeros((), jnp.float32)),
        )

        def slot_b_feed(m_b):
            return jnp.clip(m_b, 0, m - 1)

        def tick(carry, t):
            # ---------------- forward slot: microbatch t - stage ----------
            m_f = t - stage_idx
            f_valid = jnp.logical_and(m_f >= 0, m_f < m)
            f_validf = f_valid.astype(jnp.float32)
            feed = jax.lax.dynamic_index_in_dim(
                x_feed, jnp.clip(m_f, 0, m - 1), keepdims=False
            )
            x_in = jnp.where(stage_idx == 0, feed, carry['x_f'])
            x_in = x_in * f_validf.astype(x_in.dtype)
            y = self.stage.apply({'params': sp}, x_in)
            y = y * f_validf.astype(y.dtype)
            # store the stage input for the backward recompute
            slot_f = jnp.clip(m_f, 0, m - 1) % ring
            resid = jax.lax.dynamic_update_index_in_dim(
                carry['resid'],
                jnp.where(f_valid, x_in, jax.lax.dynamic_index_in_dim(
                    carry['resid'], slot_f, keepdims=False)),
                slot_f, 0,
            )

            # last stage: head + loss + cotangent for this microbatch, the
            # same tick its forward completes (the 1F1B pivot). Other
            # stages skip the head entirely — they are off the tick's
            # critical path while the last stage computes it.
            tgt = jax.lax.dynamic_index_in_dim(
                t_feed, jnp.clip(m_f, 0, m - 1), keepdims=False
            )

            def do_head(_):
                lval, pull = jax.vjp(head_loss, y, head_params, lnf_params, tgt)
                ybar, hbar, lbar, _ = pull(f_validf)
                return lval * f_validf, ybar, hbar, lbar

            def no_head(_):
                # fresh zeros are unvarying; pcast so both branches agree
                return jax.tree_util.tree_map(
                    lambda v: jax.lax.pcast(
                        jnp.zeros(v.shape, v.dtype), all_axes, to='varying'
                    ),
                    (
                        jnp.zeros((), jnp.float32),
                        jnp.zeros_like(y),
                        jax.tree_util.tree_map(
                            lambda v: jnp.zeros_like(v, jnp.float32),
                            head_params,
                        ),
                        jax.tree_util.tree_map(
                            lambda v: jnp.zeros_like(v, jnp.float32),
                            lnf_params,
                        ),
                    ),
                )

            lval, ybar_local, hbar, lbar = jax.lax.cond(
                stage_idx == n - 1, do_head, no_head, None
            )

            # ---------------- backward slot: microbatch t - (2S-2-stage) --
            m_b = t - (2 * n - 2 - stage_idx)
            b_valid = jnp.logical_and(m_b >= 0, m_b < m)
            b_validf = b_valid.astype(jnp.float32)
            slot_b = jnp.clip(m_b, 0, m - 1) % ring
            x_saved = jax.lax.dynamic_index_in_dim(resid, slot_b, keepdims=False)
            # cotangent: in-tick on the last stage (m_b == m_f there), the
            # ppermuted one from stage s+1 elsewhere
            ybar = jnp.where(stage_idx == n - 1, ybar_local, carry['g_b'])
            ybar = ybar * b_validf.astype(ybar.dtype)
            y_re, pull, tick_a = jax.vjp(
                lambda sp_, x_, gd_: self._stage_apply_captured(
                    sp_, gd_, x_, b_validf
                ),
                sp, x_saved, gst, has_aux=True,
            )
            del y_re
            spbar, xbar_mb, gdbar = pull(ybar)

            carry = dict(
                x_f=jax.lax.ppermute(y, PIPE_AXIS, fwd_perm),
                g_b=jax.lax.ppermute(
                    xbar_mb.astype(self.dtype), PIPE_AXIS, bwd_perm
                ),
                resid=resid,
                xbar=jax.lax.dynamic_update_index_in_dim(
                    carry['xbar'],
                    jnp.where(
                        jnp.logical_and(stage_idx == 0, b_valid),
                        xbar_mb.astype(self.dtype),
                        jax.lax.dynamic_index_in_dim(
                            carry['xbar'], slot_b_feed(m_b), keepdims=False
                        ),
                    ),
                    slot_b_feed(m_b), 0,
                ),
                loss=carry['loss'] + lval,
                sgrads=jax.tree_util.tree_map(
                    lambda acc, new: acc + new, carry['sgrads'], spbar
                ),
                hgrads=jax.tree_util.tree_map(
                    lambda acc, new: acc + new, carry['hgrads'], hbar
                ),
                lgrads=jax.tree_util.tree_map(
                    lambda acc, new: acc + new, carry['lgrads'], lbar
                ),
                a_acc={k: carry['a_acc'][k] + tick_a[k] for k in tick_a},
                g_acc={k: carry['g_acc'][k] + gdbar[k] for k in gdbar},
                n_b=carry['n_b'] + b_validf,
            )
            return carry, None

        carry, _ = jax.lax.scan(tick, carry0, jnp.arange(ticks))

        loss_sum = jax.lax.psum(carry['loss'], all_axes)
        sgrads = carry['sgrads']
        hgrads = jax.tree_util.tree_map(
            lambda v: jax.lax.psum(v, all_axes), carry['hgrads']
        )
        lgrads = jax.tree_util.tree_map(
            lambda v: jax.lax.psum(v, all_axes), carry['lgrads']
        )
        a_acc, g_acc, n_b = carry['a_acc'], carry['g_acc'], carry['n_b']
        if self.data_axes:
            # DP reductions: stage grads and factor stats sum over the data
            # peers (the reference's factor allreduce over the DP group)
            sgrads = jax.tree_util.tree_map(
                lambda v: jax.lax.psum(v, self.data_axes), sgrads
            )
            a_acc = {
                k: jax.lax.psum(v, self.data_axes) for k, v in a_acc.items()
            }
            g_acc = {
                k: jax.lax.psum(v, self.data_axes) for k, v in g_acc.items()
            }
            n_b = jax.lax.psum(n_b, self.data_axes)
        ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
        # xbar holds real cotangents on stage 0 and zeros elsewhere: the
        # psum over pipe is the broadcast of stage 0's buffer to the world
        xbar = jax.lax.psum(carry['xbar'], PIPE_AXIS)
        return (
            loss_sum,
            ex(sgrads),
            hgrads,
            lgrads,
            ex(a_acc),
            ex(g_acc),
            n_b[None],
            xbar,
        )

    def _loss_and_stats_1f1b(self, params, batch):
        """1F1B: loss, grads, and capture stats from ONE combined scan."""
        tokens, targets = batch
        b, s = tokens.shape
        m = self.n_microbatches
        self._validate_batch(b)
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
        tspec = bspec
        out = jax.shard_map(
            self._body_1f1b,
            mesh=self.mesh,
            axis_names=self._manual,  # model stays automatic (TP via GSPMD)
            in_specs=(P(PIPE_AXIS), P(), P(), bspec, tspec, gspec),
            out_specs=(
                P(),                # loss (psum'd)
                jax.tree_util.tree_map(lambda _: P(PIPE_AXIS),
                                       params['stages']),
                P(),                # head grads (psum'd)
                P(),                # ln_f grads (psum'd)
                {k: P(PIPE_AXIS) for k in gstats0},
                {k: P(PIPE_AXIS) for k in gstats0},
                P(PIPE_AXIS),       # counts
                bspec,              # xbar feed
            ),
        )(params['stages'], params['head'], params['ln_f'], x_feed, t_feed,
          gstats0)
        loss, sgrads, hgrads, lgrads, a_stats, g_stats, counts, xbar = out
        (egrads,) = embed_pull(xbar)
        grads = {
            'embed': egrads['embed'],
            'pos_embed': egrads['pos_embed'],
            'stages': sgrads,
            'head': hgrads,
            'ln_f': lgrads,
        }
        denom = jnp.maximum(counts, 1.0)
        a_avg = {k: v / denom[:, None, None] for k, v in a_stats.items()}
        # g-tap cotangents carry the 1/total_tokens loss normalization; the
        # per-count division matches the gpipe path's convention
        g_avg = {k: v / denom[:, None, None] for k, v in g_stats.items()}
        return loss, grads, capture_lib.CapturedStats(a=a_avg, g=g_avg)

    # ------------------------------------------------------------- loss

    def loss_and_stats(self, params, batch):
        """(loss, grads, stage-stacked stats) in one backward pass."""
        if self.schedule == '1f1b':
            return self._loss_and_stats_1f1b(params, batch)

        def tapped(params, gstats):
            tokens, targets = batch
            logits, a_stats, counts = self.apply(params, tokens, gstats)
            nll = losses_lib.vocab_parallel_nll(logits, targets)
            return jnp.mean(nll), (a_stats, counts)

        gstats0 = self.zero_gstats()
        (loss, (a_stats, counts)), (grads, g_stats) = jax.value_and_grad(
            tapped, argnums=(0, 1), has_aux=True
        )(params, gstats0)
        denom = jnp.maximum(counts, 1.0)  # (n_stages,)
        a_avg = {
            k: v / denom[:, None, None] for k, v in a_stats.items()
        }
        g_avg = {
            k: v / denom[:, None, None] for k, v in g_stats.items()
        }
        return loss, grads, capture_lib.CapturedStats(a=a_avg, g=g_avg)


@dataclasses.dataclass
class PipelineKFAC:
    """K-FAC for a :class:`PipelinedLM`'s stage layers.

    State arrays keep the leading stage axis sharded over ``pipe``: factor
    updates, decompositions, and preconditioning all run inside one
    shard_map with zero cross-stage traffic (the reference's
    MEM-OPT-among-pipe-peers, kfac/gpt_neox/assignment.py:116-130). The
    kl-clip sum is the only cross-stage collective (one psum).

    Both compute methods are supported: EIGEN (eigendecompositions in the
    ``qa/qg/da/dg`` slots) and INVERSE (damped inverses in ``qa/qg``,
    solver per ``config.inverse_solver`` — ``'newton_schulz'`` keeps
    pipelined K-FAC entirely matmul-based on TPU).
    """

    config: KFACPreconditioner
    model: PipelinedLM

    def __post_init__(self) -> None:
        from kfac_tpu import enums

        self.mesh = self.model.mesh
        self.registry = self.model.stage_registry
        self.n_stages = self.model.n_stages
        # DP axes of a pipeline_mesh: each stage's eigendecompositions
        # round-robin over these peers instead of being recomputed by every
        # data replica (eigh work / dp wall-clock), then psum-share. The
        # model axis stays automatic (factors/decomps are global over TP),
        # mirroring PipelinedLM's manual set.
        self._dp_axes = tuple(
            ax
            for ax in self.mesh.axis_names
            if ax not in (PIPE_AXIS, mesh_lib.MODEL_AXIS)
            and int(self.mesh.shape[ax]) > 1
        )
        self._manual = frozenset(
            ax
            for ax in self.mesh.axis_names
            if ax != mesh_lib.MODEL_AXIS
        )
        self._dp_size = 1
        for ax in self._dp_axes:
            self._dp_size *= int(self.mesh.shape[ax])
        self._eigen = self.config.compute_method == enums.ComputeMethod.EIGEN
        if self.config.prediv_eigenvalues:
            raise NotImplementedError(
                'prediv_eigenvalues is not supported by PipelineKFAC'
            )

    def _peer_index(self):
        """Linear index of this device within the DP axes (inside shard_map)."""
        idx = jnp.asarray(0, jnp.int32)
        for ax in self._dp_axes:
            idx = idx * int(self.mesh.shape[ax]) + jax.lax.axis_index(ax)
        return idx

    def _make_decomp(self, damping, floor, a_mat, g_mat, like, li):
        """Decomposition of one stage-local layer (inside shard_map).

        Returns ``compute(operand) -> (qa, qg, da, dg)``: eigendecomposition
        (EIGEN) or damped inverses in the qa/qg slots (INVERSE — the
        Newton-Schulz solver keeps this matmul-only on TPU). With DP peers
        present the work round-robins over them by layer index ``li`` and
        psum-shares, dividing decomposition wall-clock by the DP world.
        ``like`` supplies zero templates for the non-owner branch; ``floor``
        is ``factors.identity_floor`` at this step, for a cold
        Newton-Schulz solve.
        """
        cfg = self.config

        def run_eigh(_):
            adec = factors_lib.compute_eigh(a_mat, cfg.inv_dtype, cfg.eigh_impl)
            gdec = factors_lib.compute_eigh(g_mat, cfg.inv_dtype, cfg.eigh_impl)
            return adec.q, gdec.q, adec.d, gdec.d

        def run_inverse(_):
            # like[0]/like[1] are the resident inverses on the INVERSE
            # path (the qa/qg slots double as a_inv/g_inv): warm-start
            # Newton-Schulz from them (safeguarded; zeros cold-start)
            inv = lambda f, prev: factors_lib.damped_inverse(
                f, damping, cfg.inv_dtype, cfg.inverse_solver,
                cfg.newton_schulz_iters, x0=prev, floor=floor,
            )
            return (
                inv(a_mat, like[0]), inv(g_mat, like[1]),
                jnp.zeros_like(like[2]), jnp.zeros_like(like[3]),
            )

        run_decomp = run_eigh if self._eigen else run_inverse
        if not self._dp_axes:
            return run_decomp
        owner = li % self._dp_size

        def vary(t):
            return jax.lax.pcast(t, self._dp_axes, to='varying')

        def dp_compute(_):
            out = jax.lax.cond(
                self._peer_index() == owner,
                lambda _: tuple(map(vary, run_decomp(None))),
                lambda _: tuple(
                    vary(jnp.zeros_like(t)) for t in like
                ),
                None,
            )
            return tuple(jax.lax.psum(t, self._dp_axes) for t in out)

        return dp_compute

    def rematerialize(self, state):
        """Recompute all decompositions from the stored factors (used by
        checkpoint restore: only step + factors are durable)."""
        cfg = self.config
        damping = _resolve(cfg.damping, state['step'])
        floor = factors_lib.identity_floor(
            state['step'], cfg.factor_decay, cfg.factor_update_steps
        )
        names = list(self.registry.layers)

        def body(a, g, qa, qg, da, dg):
            sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)
            a, g, qa, qg, da, dg = map(sq, (a, g, qa, qg, da, dg))
            new_qa, new_qg, new_da, new_dg = {}, {}, {}, {}
            for li, name in enumerate(names):
                compute = self._make_decomp(
                    damping, floor, a[name], g[name],
                    (qa[name], qg[name], da[name], dg[name]), li,
                )
                (
                    new_qa[name], new_qg[name],
                    new_da[name], new_dg[name],
                ) = compute(None)
            ex = lambda t: jax.tree_util.tree_map(lambda x: x[None], t)
            return ex(new_qa), ex(new_qg), ex(new_da), ex(new_dg)

        specs = tuple({k: P(PIPE_AXIS) for k in names} for _ in range(6))
        new_qa, new_qg, new_da, new_dg = jax.shard_map(
            body,
            mesh=self.mesh,
            in_specs=specs,
            out_specs=specs[:4],
            axis_names=self._manual,
        )(
            state['a'], state['g'], state['qa'], state['qg'],
            state['da'], state['dg'],
        )
        return {
            **state,
            'qa': new_qa, 'qg': new_qg, 'da': new_da, 'dg': new_dg,
        }

    def describe(self) -> str:
        """Registration + placement dump (reference parity:
        kfac/preconditioner.py:264-268,300): stage topology and the
        stage-local MEM-OPT placement."""
        lines = [
            f'PipelineKFAC: {len(self.registry.layers)} layers per stage '
            f'x {self.n_stages} stages (mesh {dict(self.mesh.shape)}), '
            'placement=MEM-OPT among pipe peers (stage-local state), '
            f'decomposition round-robin over dp={self._dp_size}, '
            f'method={self.config.compute_method.name}',
            self.config.describe(),
        ]
        return '\n'.join(lines)

    def extract_factors(self, state) -> dict[str, dict[str, jax.Array]]:
        """Per-layer factors with their stage axis (portable across
        pipeline engine configs with the SAME n_stages; cross-stage-count
        migration would need a stage re-partition, which the reference
        does not support either)."""
        return {
            name: {'a': state['a'][name], 'g': state['g'][name]}
            for name in state['a']
        }

    def insert_factors(self, state, factors):
        """Inverse of :meth:`extract_factors`; call
        :meth:`rematerialize` afterwards."""
        new = {
            **state,
            'a': dict(state['a']),
            'g': dict(state['g']),
        }
        spec = self._spec()
        for name, fg in factors.items():
            if name in new['a']:
                new['a'][name] = jax.device_put(
                    fg['a'].astype(self.config.factor_dtype), spec
                )
                new['g'][name] = jax.device_put(
                    fg['g'].astype(self.config.factor_dtype), spec
                )
        return new

    def _spec(self):
        return NamedSharding(self.mesh, P(PIPE_AXIS))

    def init(self):
        def build():
            a, g, qa, qg, da, dg = {}, {}, {}, {}, {}, {}
            ns = self.n_stages
            cfg = self.config
            for name, h in self.registry.layers.items():
                na, ng = h.a_factor_shape[0], h.g_factor_shape[0]
                a[name] = jnp.broadcast_to(
                    jnp.eye(na, dtype=cfg.factor_dtype), (ns, na, na)
                )
                g[name] = jnp.broadcast_to(
                    jnp.eye(ng, dtype=cfg.factor_dtype), (ns, ng, ng)
                )
                qa[name] = jnp.zeros((ns, na, na), cfg.inv_dtype)
                qg[name] = jnp.zeros((ns, ng, ng), cfg.inv_dtype)
                da[name] = jnp.zeros((ns, na), cfg.inv_dtype)
                dg[name] = jnp.zeros((ns, ng), cfg.inv_dtype)
            return {
                'step': jnp.asarray(0, jnp.int32),
                'a': a, 'g': g, 'qa': qa, 'qg': qg, 'da': da, 'dg': dg,
            }

        state = build()
        spec = self._spec()
        for key in ('a', 'g', 'qa', 'qg', 'da', 'dg'):
            state[key] = {
                k: jax.device_put(v, spec) for k, v in state[key].items()
            }
        # `step` must live on the full pipe mesh (replicated), not a single
        # device: leaving it unplaced commits it to device 0 and any jit over
        # (params-on-mesh, state) fails with incompatible-devices. Restore
        # inherits this placement because orbax restores each leaf onto the
        # template sharding, and checkpoint.restore templates from init().
        state['step'] = jax.device_put(
            state['step'], NamedSharding(self.mesh, P())
        )
        return state

    def step(self, state, grads, stats):
        """Update factors/decomps and precondition stage grads (in place of
        the stage slice of ``grads``)."""
        cfg = self.config
        step = state['step']
        damping = _resolve(cfg.damping, step)
        alpha = _resolve(cfg.factor_decay, step)
        lr = _resolve(cfg.lr, step)
        names = list(self.registry.layers)
        helpers = self.registry.layers

        do_factors = step % _resolve(cfg.factor_update_steps, step) == 0
        do_inverses = step % _resolve(cfg.inv_update_steps, step) == 0
        floor = factors_lib.identity_floor(
            step, cfg.factor_decay, cfg.factor_update_steps
        )

        def body(a, g, qa, qg, da, dg, sa, sg, stage_grads):
            # stage-local views: leading dim = stages per rank (1 for the
            # plain pipeline, virtual_chunks for the interleaved one —
            # a static Python loop over local chunks keeps the per-stage
            # math identical; the kl-clip sum spans all chunks of all
            # ranks before any scaling)
            local = next(iter(a.values())).shape[0]
            per_ci: list[tuple] = []
            vg = jnp.zeros((), jnp.float32)
            for ci in range(local):
                sq = lambda t: jax.tree_util.tree_map(lambda x: x[ci], t)
                a_c, g_c, qa_c, qg_c, da_c, dg_c, sa_c, sg_c = map(
                    sq, (a, g, qa, qg, da, dg, sa, sg)
                )
                sgrads = sq(stage_grads)
                new_a, new_g = {}, {}
                new_qa, new_qg, new_da, new_dg = {}, {}, {}, {}
                pre = {}
                for li, name in enumerate(names):
                    h = helpers[name]
                    na_ = jax.lax.cond(
                        do_factors,
                        lambda _: factors_lib.ema_update(
                            a_c[name], sa_c[name].astype(cfg.factor_dtype),
                            alpha,
                        ),
                        lambda _: a_c[name],
                        None,
                    )
                    ng_ = jax.lax.cond(
                        do_factors,
                        lambda _: factors_lib.ema_update(
                            g_c[name], sg_c[name].astype(cfg.factor_dtype),
                            alpha,
                        ),
                        lambda _: g_c[name],
                        None,
                    )
                    new_a[name], new_g[name] = na_, ng_

                    # round-robin owner over DP peers: offset by chunk so
                    # multi-chunk ranks spread decompositions too
                    compute = self._make_decomp(
                        damping, floor, na_, ng_,
                        (qa_c[name], qg_c[name], da_c[name], dg_c[name]),
                        ci * len(names) + li,
                    )
                    qa_, qg_, da_, dg_ = jax.lax.cond(
                        do_inverses,
                        compute,
                        lambda _: (
                            qa_c[name], qg_c[name], da_c[name], dg_c[name]
                        ),
                        None,
                    )
                    new_qa[name], new_qg[name] = qa_, qg_
                    new_da[name], new_dg[name] = da_, dg_

                    path = self.registry.param_paths[name]
                    node = sgrads
                    for k in path:
                        node = node[k]
                    gmat = h.grads_to_matrix(dict(node))
                    if self._eigen:
                        pmat = factors_lib.eigen_preconditioned_grad(
                            gmat,
                            factors_lib.EigenDecomp(qa_, da_),
                            factors_lib.EigenDecomp(qg_, dg_),
                            damping,
                        )
                    else:
                        pmat = factors_lib.inverse_preconditioned_grad(
                            gmat, qa_, qg_
                        )
                    if cfg.kl_clip is not None:
                        vg = vg + factors_lib.kl_clip_terms(
                            pmat, gmat, lr
                        )
                    pre[name] = pmat
                per_ci.append(
                    (new_a, new_g, new_qa, new_qg, new_da, new_dg,
                     sgrads, pre)
                )

            if cfg.kl_clip is not None:
                vg = jax.lax.psum(vg, PIPE_AXIS)
                scale = factors_lib.kl_clip_scale(
                    vg, _resolve(cfg.kl_clip, step)
                )
            else:
                scale = 1.0

            out_per_ci = []
            for new_a, new_g, new_qa, new_qg, new_da, new_dg, sgrads, pre \
                    in per_ci:
                out_grads = sgrads
                for name in names:
                    h = helpers[name]
                    new_leaves = h.matrix_to_grads(
                        factors_lib.kl_clip_apply(pre[name], scale)
                    )
                    out_grads = registry_lib.merge_layer_grads(
                        out_grads, {name: new_leaves},
                        registry_lib.Registry(
                            layers={name: h},
                            param_paths={
                                name: self.registry.param_paths[name]
                            },
                        ),
                    )
                out_per_ci.append(
                    (new_a, new_g, new_qa, new_qg, new_da, new_dg,
                     out_grads)
                )
            stack = lambda *ts: jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *ts
            )
            return tuple(
                stack(*(out_per_ci[ci][j] for ci in range(local)))
                for j in range(7)
            )

        # 8 stage-sharded dict specs: a, g, qa, qg, da, dg, stats.a, stats.g
        state_specs = tuple({k: P(PIPE_AXIS) for k in names} for _ in range(8))
        grads_spec = jax.tree_util.tree_map(
            lambda _: P(PIPE_AXIS), grads['stages']
        )
        new_a, new_g, new_qa, new_qg, new_da, new_dg, new_stage_grads = (
            jax.shard_map(
                body,
                mesh=self.mesh,
                in_specs=state_specs + (grads_spec,),
                out_specs=state_specs[:6] + (grads_spec,),
                axis_names=self._manual,
            )(
                state['a'], state['g'], state['qa'], state['qg'],
                state['da'], state['dg'], stats.a, stats.g, grads['stages'],
            )
        )
        new_state = {
            'step': step + 1,
            'a': new_a, 'g': new_g, 'qa': new_qa, 'qg': new_qg,
            'da': new_da, 'dg': new_dg,
        }
        new_grads = dict(grads)
        new_grads['stages'] = new_stage_grads
        return new_state, new_grads
