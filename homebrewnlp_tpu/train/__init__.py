"""Trainer: jit-compiled sharded train step.

Replaces the reference's TF1 session loop + TPUEstimator machinery
(/root/reference/src/run/run.py:220-262) with a single donated
``jax.jit`` step over a NamedSharding mesh:

- macro-batching (reference src/run/train.py:21-75 unrolled N model replicas
  in one graph, assigning only on the last slice) becomes a ``lax.scan`` over
  macro slices carrying (variables, optimizer state) — sequential optimizer
  steps per device step, identical update semantics, O(1) graph size.
- true gradient accumulation (scaffolded but rejected by the reference,
  src/dataclass.py:189-191) is supported: mean grads over
  ``grad_accumulation`` scan steps, then one update.
- multi-loss strategies linear / pcgrad / mgda (src/run/train.py:44-47).
"""
from __future__ import annotations

import collections
import contextlib
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..config import ModelParameter
from ..core import sharding as shardlib
from ..model import Model
from ..optim import Optimizer
from ..optim.gradients import MULTI_LOSS_GRADIENTS
from ..telemetry import memory

Params = typing.Dict[str, jax.Array]


@contextlib.contextmanager
def _local_batch_dims(p: ModelParameter, local: int):
    """Rebind the config's batch-sized dims to one data shard's slice for
    the duration of a trace (the bucketed policy's manual region traces the
    model on a per-shard batch; ``Dim`` is frozen, so the shape LISTS that
    embed the batch dim are rebuilt).  Text-only — the policy's
    eligibility gate excludes video configs, whose frame shapes also carry
    the batch dim."""
    from ..core.dims import Dim

    saved = (p.train_batch_size, p.batch_dim, p.macro_batch_dim,
             p.token_dim_shape, p.input_pipeline_shape)
    bd = Dim("batch", local)
    p.train_batch_size = local
    p.batch_dim = bd
    p.macro_batch_dim = Dim("batch", local * p.macro_batching)
    p.token_dim_shape = [bd if d.name == "batch" else d
                         for d in p.token_dim_shape]
    p.input_pipeline_shape = {
        k: [bd if getattr(d, "name", None) == "batch" else d for d in v]
        if isinstance(v, list) else v
        for k, v in p.input_pipeline_shape.items()}
    try:
        yield
    finally:
        (p.train_batch_size, p.batch_dim, p.macro_batch_dim,
         p.token_dim_shape, p.input_pipeline_shape) = saved


def _info_metrics(info) -> typing.Dict[str, jax.Array]:
    """Loss/accuracy metrics from a model BuildInfo (None -> 0), and what
    its layers reported of themselves (``LossInfo.layer_stats``): layer
    moe's worst expert load and the (token, choice) pairs it routed (and
    under top-1 its chosen probability), layer cca's logit bound, layer
    mamba's most negative within-chunk cumulative log-decay, layer
    gated_delta's largest solved transform."""
    stats = getattr(info, "layer_stats", None) or {}
    extra = {}
    if "moe_routed_pairs" in stats:
        extra = {"moe_load_max_over_mean":
                 jnp.max(stats["moe_load_max_over_mean"]),
                 "moe_routed_pairs": jnp.sum(stats["moe_routed_pairs"])}
    if "moe_held_pairs" in stats:
        # layers that hold a share of the experts: the pairs routed to the
        # held ones, and their share of the pairs routed, over all such
        # layers and in the layer where it is largest
        extra["moe_held_pairs"] = jnp.sum(stats["moe_held_pairs"])
        extra["moe_held_pair_share"] = extra["moe_held_pairs"] \
            / extra["moe_routed_pairs"]
        extra["moe_held_pair_share_max"] = jnp.max(
            stats["moe_held_pairs"] / stats["moe_routed_pairs"])
    if "moe_top1_weight_mean" in stats:
        # the layer whose router says least
        extra["moe_top1_weight_mean"] = jnp.min(stats["moe_top1_weight_mean"])
    if "cca_logit_scale" in stats:
        extra["cca_logit_scale_max"] = jnp.max(stats["cca_logit_scale"])
    if "ssd_log_decay_min" in stats:
        extra["ssd_log_decay_min"] = jnp.min(stats["ssd_log_decay_min"])
    if "delta_transform_abs_max" in stats:
        extra["delta_transform_abs_max"] = jnp.max(
            stats["delta_transform_abs_max"])
    return {
        **extra,
        "loss": info.total_loss.data.astype(jnp.float32),
        "token_loss": (info.token_loss.data.astype(jnp.float32)
                       if info.token_loss is not None else jnp.float32(0)),
        "video_loss": (info.video_loss.data.astype(jnp.float32)
                       if info.video_loss is not None else jnp.float32(0)),
        "accuracy": (info.accuracy.data.astype(jnp.float32)
                     if info.accuracy is not None else jnp.float32(0)),
    }


def _grad_norm_metrics(grads: Params, debug: bool) -> typing.Dict[str, jax.Array]:
    extra = {}
    if debug:
        # per-variable gradient norms (the reference's --debug_grad
        # histogram summaries, src/run/run.py:147-153)
        extra = {f"grad_norm/{k}": jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
                 for k, g in grads.items()}
    extra["global_grad_norm"] = jnp.sqrt(sum(
        jnp.sum(g.astype(jnp.float32) ** 2) for g in grads.values()))
    return extra


#: step metrics the layers report of themselves (``_info_metrics``) and what
#: ``Trainer._publish_layer_stats`` publishes each as
_LAYER_STATS = {
    "moe_load_max_over_mean": (
        "gauge", "hbnlp_moe_load_max_over_mean",
        "pairs of the busiest expert over the mean, worst moe layer of the "
        "newest finished step"),
    "moe_routed_pairs": (
        "counter", "hbnlp_moe_routed_pairs_total",
        "(token, choice) pairs routed to an expert, all moe layers"),
    "moe_held_pairs": (
        "counter", "hbnlp_moe_held_pairs_total",
        "(token, choice) pairs routed to an expert this rank holds, all moe "
        "layers that hold a share of the experts"),
    "moe_held_pair_share": (
        "gauge", "hbnlp_moe_held_pair_share",
        "pairs routed to held experts over pairs routed, all moe layers of "
        "the newest finished step (experts_held / experts when balanced)"),
    "moe_held_pair_share_max": (
        "gauge", "hbnlp_moe_held_pair_share_max",
        "the same share in the moe layer where it is largest: how far the "
        "static row buffer (hbnlp_moe_held_rows_bound) is filled is this "
        "times moe_top_k / min(moe_top_k, experts_held)"),
    "moe_top1_weight_mean": (
        "gauge", "hbnlp_moe_top1_weight_mean",
        "mean probability of the chosen expert over the tokens of the newest "
        "finished step, in the top-1 moe layer where it is smallest "
        "(1 / experts = a router that says nothing)"),
    "cca_logit_scale_max": (
        "gauge", "hbnlp_cca_logit_scale_max",
        "largest sqrt(features_per_head) * |tau| over the cca layers of the "
        "newest finished step: q and k have unit direction, so no attention "
        "logit passes it"),
    "ssd_log_decay_min": (
        "gauge", "hbnlp_ssd_log_decay_min",
        "most negative within-chunk cumulative dt * A of the newest finished "
        "step, all mamba layers: exp of it is the smallest decay the chunked "
        "scan formed"),
    "delta_transform_abs_max": (
        "gauge", "hbnlp_delta_transform_abs_max",
        "largest magnitude in any chunk's solved transform T = (I + "
        "strict_tril(diag(beta) (K K^T o Gamma)))^-1 diag(beta) of the newest "
        "finished step, all gated_delta layers: what its lower-precision "
        "matmul operands have to carry"),
}


#: ``Trainer._loaded_probe`` before the first step is dispatched
_FIRST_STEP = object()


class TrainState(typing.NamedTuple):
    variables: Params
    opt_state: typing.Dict[str, typing.Dict[str, jax.Array]]
    step: jax.Array


class Trainer:
    def __init__(self, params: ModelParameter, model: Model,
                 mesh: typing.Optional[jax.sharding.Mesh] = None):
        self.params = params
        self.model = model
        self.mesh = mesh
        self.optimizer: typing.Optional[Optimizer] = None
        self._step_fn = None
        self._stats_fn = None
        self._eval_fn = None
        self._rng_counter = 0
        # per-step spans observe the registry only under telemetry_enabled
        # (zero registry calls on the hot path when off); the trace
        # annotation is written either way
        self._record_steps = bool(params.telemetry_enabled)
        # the layers' own statistics of steps already dispatched, waiting
        # for the device to finish them (_publish_layer_stats)
        self._pending_layer_stats: collections.deque = collections.deque()
        # resolved lazily on the first traced step (warns once on fallback)
        self._grad_allreduce_resolved: typing.Optional[str] = None
        # the chip's memory (telemetry/memory.py): the start-up line of
        # init_state's marks, and the one ``step`` leaves here at its mark
        # (None until then, and on a backend that reports nothing);
        # ``train()`` prints both.  ``_loaded_probe``: the first step's
        # loss once that step is dispatched, None once the mark is made
        self.state_memory_line: str = memory.NOT_REPORTED
        self.step_memory_line: typing.Optional[str] = None
        self._loaded_probe: typing.Any = _FIRST_STEP

    # -- state -------------------------------------------------------------
    def init_state(self, batch: typing.Dict[str, jax.Array],
                   seed: typing.Optional[int] = None) -> TrainState:
        one = {k: v[0] if self.params.macro_batching > 1 else v
               for k, v in batch.items()}
        if jax.process_count() > 1 and self.mesh is not None:
            # the caller feeds its per-process slice; the model traces (and
            # the jit step sees) the assembled GLOBAL batch shape (local x
            # the number of distinct data-axis slices).  init is abstract
            # (eval_shape) so only shape/dtype matter — np.empty avoids
            # materialising a global-batch copy
            _, slice_count = shardlib.process_data_slice(self.mesh)
            one = {k: np.empty((np.asarray(v).shape[0] * slice_count,)
                               + np.asarray(v).shape[1:],
                               np.asarray(v).dtype)
                   for k, v in one.items()}
        variables = self.model.init(one, seed)
        self.optimizer = Optimizer(self.params, self.model.param_dims)
        # set-up spans (once a run, always recorded; docs/OBSERVABILITY.md):
        # with setup/model_init they split what a caller's clock around
        # init_state sees
        with telemetry.span("setup/place_params"):
            if self.mesh is not None:
                variables = shardlib.shard_params(
                    self.params, variables, self.model.param_dims, self.mesh)
            else:
                variables = {k: jnp.asarray(v) for k, v in variables.items()}
        memory.mark("params_placed")
        with telemetry.span("setup/opt_init"):
            opt_state = self.optimizer.init(variables)
        step = jnp.asarray(self.params.current_step, jnp.int32)
        if self.mesh is not None:
            # committed and replicated like every other leaf: the step
            # comes back on the mesh, and an uncommitted one going in would
            # make the second call a different (re-compiled) program
            step = jax.device_put(step, jax.sharding.NamedSharding(
                self.mesh, jax.sharding.PartitionSpec()))
        state = TrainState(variables, opt_state, step)
        with telemetry.span("setup/init_wait"):
            # placement and slot building are asynchronous: wait here, where
            # every caller waits anyway, so that what the device still owes
            # is not charged to whatever the caller does next
            jax.block_until_ready(state)
        # parameters + optimizer slots + the caller's first batch, before
        # anything a caller does next can allocate
        self.state_memory_line = memory.publish_state(
            memory.mark("state_ready"),
            {"params": variables.values(),
             "opt_slots": jax.tree_util.tree_leaves(opt_state)})
        return state

    # -- one micro step ----------------------------------------------------
    def _1f1b_exclusion(self) -> typing.Optional[str]:
        """Why a requested 1F1B schedule cannot run, or None if it can."""
        p = self.params
        if p.multi_loss_strategy in ("pcgrad", "mgda"):
            return f"multi_loss_strategy={p.multi_loss_strategy!r}"
        if not p.use_language or p.use_video:
            return "non-text (video) model"
        if p.contrastive_across_samples or p.contrastive_across_token_embeddings:
            return "contrastive loss"
        if p.train_quantized_matmuls:
            # the fused schedule builds its own per-stage vjps outside
            # _grads' quantization seam; GPipe routes through loss_of below
            return "train_quantized_matmuls"
        return None

    # -- gradient all-reduce policy (docs/DISTRIBUTED.md) -------------------
    _INHERIT = object()

    def grad_allreduce_fallback(self) -> typing.Optional[str]:
        """Why ``grad_allreduce="bucketed"`` cannot run for this config
        (None = it can).  Mirrors ``_1f1b_exclusion``: the policy refuses
        loudly instead of silently changing the program."""
        p = self.params
        if p.grad_allreduce != "bucketed":
            return None
        if self.mesh is None:
            return "single-device run (no data axis to reduce over)"
        if self.mesh.shape.get(shardlib.PIPE_AXIS, 1) > 1:
            return "pipeline mesh (the schedules build their own grads)"
        if self.mesh.shape.get(shardlib.SEQUENCE_AXIS, 1) > 1:
            # ring attention is itself a shard_map over 'sequence'; nesting
            # it inside the data-manual wrapper is unsupported
            return "sequence-parallel mesh (nested shard_map)"
        if p.multi_loss_strategy in ("pcgrad", "mgda"):
            return f"multi_loss_strategy={p.multi_loss_strategy!r}"
        if p.grad_accumulation > 1:
            return "grad_accumulation > 1 (reduce-after-accumulate only)"
        if p.use_video or not p.use_language:
            return "non-text (video) model"
        if p.memory_reduction_strategy != "none":
            # the strategy custom_vjp backwards (and the plain native-scan
            # "save" replay) hard-abort XLA's SPMD partitioner inside a
            # partial-manual region on jax 0.4.37 (`Check failed:
            # sharding.IsManualSubgroup()` — a C++ CHECK, not catchable);
            # the jax.checkpoint-wrapped save_dots replay partitions fine.
            # Gate on the RESOLVED policy so the abort can never be reached
            from ..model.remat import resolve_remat
            if resolve_remat(p, self.mesh) != "save_dots":
                return (f"memory_reduction_strategy="
                        f"{p.memory_reduction_strategy!r} without "
                        "remat_policy=\"save_dots\" (strategy backwards "
                        "abort XLA's partial-manual partitioner on this "
                        "jax; save_dots runs the identical recurrence and "
                        "partitions cleanly)")
        return None

    def _bucket_plan(self, variables: Params
                     ) -> typing.List[typing.List[str]]:
        """Size-targeted buckets over the grad pytree in REVERSE creation
        order (parameters are created input→output, so reversed ≈ the
        order their backward contributions complete — output-side leaves
        first).  Each bucket's raveled leaves concatenate into ONE
        all-reduce buffer, so buckets are dtype-homogeneous (a cast just to
        share a collective would change the reduction numerics); a leaf
        above the target gets its own bucket."""
        target = max(1, int(self.params.grad_bucket_mb * (1 << 20)))
        mesh_shape = dict(self.mesh.shape) if self.mesh is not None else {}

        def concat_ok(name: str) -> bool:
            # only leaves REPLICATED over the auto (model) axes may share a
            # flat buffer: raveling a model-sharded leaf into a concat
            # forces GSPMD to reshard it (measured: all-to-alls + permutes
            # appear next to the bucket), which costs more than the
            # per-leaf launch the bucket was saving
            dims = self.model.param_dims.get(name, ())
            spec = shardlib.spec_for_dims(self.params, dims, self.mesh) \
                if self.mesh is not None else ()
            return not any(ax is not None and ax != shardlib.DATA_AXIS
                           and mesh_shape.get(ax, 1) > 1 for ax in spec)

        buckets: typing.List[typing.List[str]] = []
        cur: typing.List[str] = []
        size = 0
        cur_dtype = None
        for name in reversed(list(variables)):
            v = variables[name]
            dt = np.dtype(v.dtype)
            nb = int(np.prod(np.shape(v))) * dt.itemsize
            if not concat_ok(name):
                if cur:
                    buckets.append(cur)
                    cur, size = [], 0
                buckets.append([name])  # its own per-leaf collective
                continue
            if cur and (size + nb > target or dt != cur_dtype):
                buckets.append(cur)
                cur, size = [], 0
            cur.append(name)
            size += nb
            cur_dtype = dt
        if cur:
            buckets.append(cur)
        return buckets

    def _resolve_grad_allreduce(self) -> str:
        """Resolve the policy once, warning loudly on a fallback.  Called
        from ``_grads_with_policy`` AND eagerly from ``_build_step``: the
        accumulation/pipeline paths never reach the policy seam, so
        without the eager call their fallback would be silent."""
        if self._grad_allreduce_resolved is None:
            reason = self.grad_allreduce_fallback()
            if self.params.grad_allreduce == "bucketed" and reason:
                import warnings
                warnings.warn(
                    f"grad_allreduce='bucketed' requested but {reason} is "
                    "not supported by the bucketed policy; falling back to "
                    "the fused GSPMD lowering", stacklevel=3)
            self._grad_allreduce_resolved = \
                "fused" if (self.params.grad_allreduce != "bucketed"
                            or reason) else "bucketed"
        return self._grad_allreduce_resolved

    def _grads_with_policy(self, variables: Params, batch, rng):
        """``(grads, base_metrics)`` through the resolved grad_allreduce
        policy — the ONE seam ``_micro_step`` consumes, so fused stays
        bit-identical to every earlier round and bucketed swaps in the
        explicit per-bucket reduction."""
        if self._resolve_grad_allreduce() == "bucketed":
            return self._grads_bucketed(variables, batch, rng)
        grads, info = self._grads(variables, batch, rng)
        return grads, _info_metrics(info)

    def _grads_bucketed(self, variables: Params, batch, rng):
        """Per-data-shard backward + explicit per-bucket gradient
        all-reduce (``grad_allreduce="bucketed"``).

        A partial-manual shard_map (manual over 'data', GSPMD-auto over
        the model axes) computes each shard's gradients from its LOCAL
        mean loss, then issues one multi-operand ``lax.psum`` per bucket
        in reverse-topological order — XLA sees n_buckets independent
        all-reduces whose operands are ready as soon as that bucket's
        backward slice completes, instead of one per-leaf pattern fused at
        the compiler's whim, so the collectives can overlap the remaining
        backward compute.  mean-of-shard-means == the global mean exactly
        in real arithmetic (equal shard sizes); floats differ only in
        reduction order (documented tolerance, tests/elastic_test.py)."""
        from jax.sharding import PartitionSpec as P

        p = self.params
        mesh = self.mesh
        nshard = mesh.shape[shardlib.DATA_AXIS]
        buckets = self._bucket_plan(variables)
        # every non-data axis of size 1 ⇒ the model interior needs no mesh
        # at all; keeping it would only leave 'data'-mentioning layout
        # rules to trip over inside the manual region
        inner_mesh = self.mesh if any(
            v > 1 for k, v in mesh.shape.items()
            if k != shardlib.DATA_AXIS) else None

        def local(vs, b, shard_rng):
            shard_rng = shard_rng[0]  # [1, 2] manual slice -> this shard's key
            # inside the manual region the model sees ONE shard's batch:
            # the config's batch-sized dims rebind to the local slice and
            # layout rules that map dims onto 'data' must not reach
            # with_sharding_constraint (the axis is manual here).  Trace-
            # time mutation, restored in finally — the established
            # eval-fn idiom (p.train)
            saved_layout = p.layout
            saved_mesh = self.mesh
            p.layout = {k: v for k, v in p.layout.items() if v != "data"}
            self.mesh = inner_mesh
            try:
                with _local_batch_dims(p, p.train_batch_size // nshard):
                    grads, info = self._grads(vs, b, shard_rng,
                                              mesh=inner_mesh)
                    metrics = _info_metrics(info)
            finally:
                p.layout = saved_layout
                self.mesh = saved_mesh
            out: typing.Dict[str, jax.Array] = {}
            for bucket in buckets:
                if len(bucket) == 1:
                    k = bucket[0]
                    out[k] = jax.lax.psum(grads[k],
                                          shardlib.DATA_AXIS) / nshard
                    continue
                # one flat buffer per bucket = ONE all-reduce launch for
                # the whole group (the DDP bucketing move); split/reshape
                # back is free data movement next to the collective
                flat = jnp.concatenate([grads[k].ravel() for k in bucket])
                red = jax.lax.psum(flat, shardlib.DATA_AXIS) / nshard
                off = 0
                for k in bucket:
                    n = int(np.prod(grads[k].shape))
                    out[k] = jax.lax.dynamic_slice_in_dim(
                        red, off, n).reshape(grads[k].shape)
                    off += n
            # metrics reduce as one scalar bundle (mean of shard means)
            names = sorted(metrics)
            packed = jax.lax.psum(
                jnp.stack([metrics[k].astype(jnp.float32) for k in names]),
                shardlib.DATA_AXIS) / nshard
            metrics = {k: packed[i] for i, k in enumerate(names)}
            return {k: out[k] for k in grads}, metrics

        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(P(), P(shardlib.DATA_AXIS), P(shardlib.DATA_AXIS)),
            out_specs=(P(), P()),
            axis_names={shardlib.DATA_AXIS}, check_vma=False)
        # one INDEPENDENT dropout stream per shard, carved outside the
        # manual region (jax 0.4.37 cannot lower axis_index under
        # partial-manual shard_map — the PartitionId gap)
        shard_rngs = jax.random.split(rng, nshard)
        return fn(variables, batch, shard_rngs)

    def _grads(self, variables: Params, batch, rng, mesh=_INHERIT):
        p = self.params
        if mesh is Trainer._INHERIT:
            mesh = self.mesh

        if (mesh is not None
                and mesh.shape.get(shardlib.PIPE_AXIS, 1) > 1
                and p.pipeline_schedule == "1f1b"):
            reason = self._1f1b_exclusion()
            if reason is None:
                # fused forward+backward schedule (loss head inside the last
                # stage); computes grads itself rather than via jax.grad
                return self.model.train_grads_1f1b(variables, batch, rng,
                                                   mesh)
            # config asked for 1f1b but an excluded feature forces GPipe —
            # say so loudly instead of silently changing the schedule
            import warnings
            warnings.warn(
                f"pipeline_schedule='1f1b' requested but {reason} is not "
                "supported by the fused schedule; falling back to GPipe "
                "(parallel/pipeline.py)", stacklevel=2)

        def loss_of(v, idx=None):
            if p.train_quantized_matmuls:
                # fake-quantize the live masters INSIDE the differentiated
                # function: the forward reads the int8 grid, the STE routes
                # every cotangent to the full-precision master
                # (core/quant.py; quality guard tests/train_quant_test.py)
                from ..core import quant as quant_mod
                v = quant_mod.quantize_for_training(
                    v, self.model.param_dims,
                    getattr(self.model, "param_fan_in", {}),
                    p.calculation_dtype)
            info = self.model.apply(v, batch, rng, mesh=mesh,
                                    layer_stats=self._record_steps)
            return (info.total_loss.data if idx is None
                    else info.loss_list[idx].data), info

        # the strategy backwards (revnet/momentum custom_vjp) re-trace
        # blocks AFTER model.apply's scope exited; without an active scope
        # the replay would see mesh=None and route attention differently
        # than the forward (flash instead of ring on a sequence-sharded
        # mesh — under stash_attention_outputs the provide would then
        # consume a ring-stashed (out, lse) pair through the flash path).
        # custom_vjp bwd rules trace synchronously inside value_and_grad,
        # so a thin mesh-bearing context keeps forward and replay routing
        # identical
        from ..core import scope as scope_mod
        grad_ctx = scope_mod.Context("apply", mesh=mesh)
        grad_ctx.matmul_accumulation = p.matmul_accumulation

        if p.multi_loss_strategy in ("pcgrad", "mgda"):
            # per-loss backward passes, combined by gradient surgery
            infos = None
            grads_per_loss = []
            n_losses = 2 if (p.use_language and p.use_video) else 1
            with scope_mod.context(grad_ctx):
                for i in range(n_losses):
                    (_, infos), g = jax.value_and_grad(
                        functools.partial(loss_of, idx=i),
                        has_aux=True)(variables)
                    grads_per_loss.append(g)
            if n_losses > 1:
                grads = MULTI_LOSS_GRADIENTS[p.multi_loss_strategy](grads_per_loss)
            else:
                grads = grads_per_loss[0]
            return grads, infos
        with scope_mod.context(grad_ctx):
            (_, info), grads = jax.value_and_grad(loss_of,
                                                  has_aux=True)(variables)
        return grads, info

    def _micro_step(self, carry, batch_rng):
        batch, rng = batch_rng
        variables, opt_state, step = carry
        grads, base_metrics = self._grads_with_policy(variables, batch, rng)
        # named-scope region: the update's ops attribute to "optimizer" in
        # HLO metadata / traces instead of blending into the model scopes
        # (docs/OBSERVABILITY.md 'Cost attribution')
        with jax.named_scope("optimizer"):
            new_vars, new_opt, lr = self.optimizer.update(variables, grads,
                                                          opt_state, step)
        metrics = {
            **_grad_norm_metrics(grads, self.params.debug_gradients),
            **base_metrics,
            "learning_rate": lr.astype(jnp.float32),
        }
        return (new_vars, new_opt, step + 1), metrics

    def _accum_step(self, carry, batch_rng):
        """True grad accumulation: average grads, single update at the end."""
        batch, rng = batch_rng
        variables, opt_state, step = carry
        p = self.params
        n = p.grad_accumulation

        def scan_fn(acc, sub):
            sub_batch, sub_rng = sub
            grads, info = self._grads(variables, sub_batch, sub_rng)
            acc = jax.tree_util.tree_map(lambda a, g: a + g.astype(jnp.float32) / n,
                                         acc, grads)
            return acc, _info_metrics(info)

        zero = {k: jnp.zeros(v.shape, jnp.float32) for k, v in variables.items()}
        grads, sub_metrics = jax.lax.scan(scan_fn, zero, (batch, rng))
        with jax.named_scope("optimizer"):
            new_vars, new_opt, lr = self.optimizer.update(variables, grads,
                                                          opt_state, step)
        metrics = {
            **_grad_norm_metrics(grads, self.params.debug_gradients),
            **{k: jnp.mean(v) for k, v in sub_metrics.items()},
            "learning_rate": lr.astype(jnp.float32)}
        return (new_vars, new_opt, step + 1), metrics

    # -- the jitted step ---------------------------------------------------
    def _build_step(self, donate: bool = True,
                    state: typing.Optional[TrainState] = None):
        """``state`` (arrays, or avals carrying shardings): under a mesh the
        new state is pinned to come back laid out exactly as this one went
        in.  Left to the compiler, reduced-shape optimizer slots (SM3's
        per-dim buckets) return sharded over 'model' although they went in
        replicated; the second step then sees new input shardings and the
        whole step compiles a second time."""
        p = self.params
        self._resolve_grad_allreduce()
        self.publish_stash_plan()

        def step_fn(state: TrainState, batch, rng):
            carry = (state.variables, state.opt_state, state.step)
            if p.macro_batching > 1:
                if p.grad_accumulation > 1:
                    ga = p.grad_accumulation
                    mb = p.macro_batching // ga
                    batch = {k: v.reshape((mb, ga) + v.shape[1:]) for k, v in batch.items()}
                    rngs = jax.random.split(rng, mb * ga).reshape(mb, ga, -1)
                    carry, metrics = jax.lax.scan(self._accum_step, carry, (batch, rngs))
                else:
                    rngs = jax.random.split(rng, p.macro_batching)
                    carry, metrics = jax.lax.scan(self._micro_step, carry, (batch, rngs))
                metrics = {**{k: jnp.mean(v) for k, v in metrics.items()},
                           "first_loss": metrics["loss"][0],
                           "last_loss": metrics["loss"][-1]}
            elif p.grad_accumulation > 1:
                ga = p.grad_accumulation
                batch = {k: v.reshape((1, ga) + v.shape[1:]) for k, v in batch.items()}
                rngs = jax.random.split(rng, ga).reshape(1, ga, -1)
                carry, metrics = jax.lax.scan(self._accum_step, carry, (batch, rngs))
                metrics = {k: jnp.mean(v) for k, v in metrics.items()}
            else:
                carry, metrics = self._micro_step(carry, (batch, rng))
            variables, opt_state, step = carry
            if p.nonfinite_loss_tolerance > 0:
                # non-finite loss guard: select the PRE-step state on-device
                # (the input state is donated, so the host cannot keep the
                # old buffers around to roll back to — the skip must live
                # inside the jitted step).  The step counter is part of the
                # select: a skipped update advances nothing.
                ok = jnp.isfinite(metrics["loss"])
                variables, opt_state, step = jax.tree_util.tree_map(
                    lambda new, old: jnp.where(ok, new, old),
                    (variables, opt_state, step),
                    (state.variables, state.opt_state, state.step))
            return TrainState(variables, opt_state, step), metrics

        out_shardings = None
        if self.mesh is not None and state is not None:
            named = jax.sharding.NamedSharding
            out_shardings = (jax.tree_util.tree_map(
                lambda x: x.sharding if isinstance(x.sharding, named)
                else None, state), None)
        # ``donate=False`` compiles the identical step without donation —
        # the HLO donation audit's negative control (analysis/entry_points)
        return jax.jit(step_fn, donate_argnums=(0,) if donate else (),
                       out_shardings=out_shardings)

    def publish_stash_plan(self) -> str:
        """``hbnlp_remat_stash_bytes{kind}`` / ``hbnlp_remat_stash_layers
        {kind}``: what rides the memory strategy's residuals in the step
        this trainer builds (model/remat.py ``stash_plan``; 0 for a kind
        that is not engaged), ``hbnlp_ssd_state_bytes``: the recurrent
        mixers' (``mamba``, ``gated_delta``) chunk states alive at once for
        the backward (``ssd_state_bytes``), and
        ``hbnlp_mamba_conv_kernel_layers``: how many of those layers took
        the Pallas conv (``conv_kernel_layers``), and
        ``hbnlp_delta_solve_kernel_layers``: how many took the Pallas pair
        for their triangular solve (``solve_kernel_layers``; 0 where no
        layer has one), and ``hbnlp_router_carry_bytes``: the router states
        carried between blocks (``router_carry_bytes``; no series where no
        layer carries one), and ``hbnlp_flash_band_layers``: the attention
        layers whose windowed flash forward is the band kernel
        (``flash_band_layers``).  Set when the step is built; returns the
        start-up line that says the same."""
        from ..model.remat import (conv_kernel_layers, flash_band_layers,
                                   moe_held_rows,
                                   router_carry_bytes, solve_kernel_layers,
                                   ssd_state_bytes, stash_line, stash_plan)
        plan = stash_plan(self.params, self.mesh)
        r = telemetry.registry()
        held_rows = moe_held_rows(self.params)
        if held_rows:
            r.gauge("hbnlp_moe_held_rows_bound",
                    "rows of the static dispatch buffer of a moe layer that "
                    "holds a share of the experts: tokens x min(moe_top_k, "
                    "experts_held), which no routing overflows"
                    ).set(held_rows)
        carry = router_carry_bytes(self.params)
        if carry:
            r.gauge("hbnlp_router_carry_bytes",
                    "bytes of the router states (layer moe, router_mlp) "
                    "alive between blocks for the backward: the carried "
                    "side value, float32 [batch, sequence, "
                    "moe_router_width] a carrying layer but the last"
                    ).set(carry)
        states = ssd_state_bytes(self.params, self.mesh)
        r.gauge("hbnlp_ssd_state_bytes",
                "per-device bytes of the recurrent mixers' (mamba, "
                "gated_delta) chunk states alive at once for the backward"
                ).set(states)
        conv_layers = conv_kernel_layers(self.params)
        r.gauge("hbnlp_mamba_conv_kernel_layers",
                "recurrent mixers (mamba, gated_delta) of the built step "
                "whose conv is the Pallas kernel pair (0 on the XLA "
                "fallback)").set(conv_layers)
        solve_layers = solve_kernel_layers(self.params)
        r.gauge("hbnlp_delta_solve_kernel_layers",
                "gated_delta layers of the built step whose triangular solve "
                "is the Pallas kernel pair (0 on the XLA blocked form, and "
                "without such a layer)").set(solve_layers or 0)
        band_layers = flash_band_layers(self.params)
        r.gauge("hbnlp_flash_band_layers",
                "attention layers of the built step whose windowed flash "
                "forward is the band kernel (0 on the tiled forward, on the "
                "CPU and without a windowed layer)").set(band_layers or 0)
        nbytes = r.gauge("hbnlp_remat_stash_bytes",
                         "per-device bytes riding the memory strategy's "
                         "residuals instead of being replayed", ("kind",))
        nlayers = r.gauge("hbnlp_remat_stash_layers",
                          "layer outputs riding the memory strategy's "
                          "residuals", ("kind",))
        for kind, (layers, size) in plan.items():
            nbytes.labels(kind).set(size)
            nlayers.labels(kind).set(layers)
        return stash_line(plan) + (
            f"; ssd chunk states {states} bytes a device; conv kernel "
            f"{conv_layers} layers" if states else "") + (
            f"; solve kernel {solve_layers} layers"
            if solve_layers is not None else "") + (
            f"; moe held rows bound {held_rows}" if held_rows else "") + (
            f"; router carry {carry} bytes" if carry else "") + (
            f"; flash band {band_layers} layers"
            if band_layers is not None else "")

    def lowered(self, state: TrainState, batch: typing.Dict[str, jax.Array]):
        """Lowered (StableHLO) train step for ``save_graph`` dumps — the
        TPU-native analogue of the reference's save_graph_def
        (src/run/run.py:171)."""
        if self._step_fn is None:
            self._step_fn = self._build_step(state=state)
        if self.mesh is not None:
            batch = shardlib.shard_batch(self.params, batch, self.mesh)
        return self._step_fn.lower(state, batch, jax.random.PRNGKey(0))

    def place_batch(self, batch: typing.Dict[str, jax.Array]
                    ) -> typing.Dict[str, jax.Array]:
        """Start the host->device transfer of one batch NOW (async on real
        accelerators): sharded placement over the mesh, or a plain
        ``device_put`` single-device.  ``step`` recognises the placed
        arrays and skips re-sharding — the seam the train loop's
        double-buffered input overlap uses (run/train_loop.py
        ``_AsyncFeeder``; ``async_input_transfer``)."""
        with telemetry.span("data/place", record=self._record_steps):
            if self.mesh is not None:
                return shardlib.shard_batch(self.params, batch, self.mesh)
            return {k: (jax.device_put(v) if v is not None else v)
                    for k, v in batch.items()}

    def _batch_placed(self, batch: typing.Dict[str, jax.Array]) -> bool:
        """True when every leaf already carries this trainer's mesh
        sharding (``place_batch`` output) — re-running shard_batch on a
        globally-assembled array would hand
        ``make_array_from_process_local_data`` a global slice and corrupt
        the batch on every multi-host layout."""
        return all(
            v is None or (isinstance(v, jax.Array)
                          and getattr(v.sharding, "mesh", None) == self.mesh)
            for v in batch.values())

    def step(self, state: TrainState, batch: typing.Dict[str, jax.Array],
             rng: typing.Optional[jax.Array] = None):
        # the host's whole part of a step — key build, placement check, the
        # jitted call's enqueue (and, the first time, its trace + compile) —
        # under one span, here and not around the call, so every caller of
        # step() has it
        with telemetry.span("train/step_dispatch", record=self._record_steps):
            if self._step_fn is None:
                self._step_fn = self._build_step(state=state)
                self._rng_counter = 0
            if rng is None:
                # host counter offset by the restored step, never a device
                # sync on state.step: a resumed run continues the
                # dropout-key sequence instead of replaying it from its
                # first step
                self._rng_counter += 1
                rng = jax.random.PRNGKey(self.params.current_step
                                         + self._rng_counter)
            if self.mesh is not None and not self._batch_placed(batch):
                batch = shardlib.shard_batch(self.params, batch, self.mesh)
            state, metrics = self._step_fn(state, batch, rng)
            if self._loaded_probe is not None:
                self._mark_step_loaded(metrics["loss"])
            if any(k in metrics for k in _LAYER_STATS):
                self._publish_layer_stats(metrics)
            return state, metrics

    def _mark_step_loaded(self, loss: jax.Array) -> None:
        """Point ``step_loaded`` of telemetry/memory.py, at the first call
        that finds the loss of the FIRST step (the one that traced,
        compiled and loaded the program) ready: the program has run once,
        so the runtime's reservation is the step's scratch and ``in_use``
        what the loop keeps.  Like ``_publish_layer_stats`` it never waits;
        once made, a step pays one ``is not None``."""
        if self._loaded_probe is _FIRST_STEP:
            self._loaded_probe = loss
        elif self._loaded_probe.is_ready():
            self._loaded_probe = None
            self.step_memory_line = memory.loaded_line(
                memory.mark("step_loaded"))

    def _publish_layer_stats(self, metrics) -> None:
        """``hbnlp_moe_load_max_over_mean``, ``hbnlp_moe_routed_pairs_total``,
        ``hbnlp_moe_held_pairs_total``, ``hbnlp_moe_held_pair_share`` (and
        ``_max``), ``hbnlp_moe_top1_weight_mean``,
        ``hbnlp_cca_logit_scale_max``, ``hbnlp_ssd_log_decay_min`` and ``hbnlp_delta_transform_abs_max`` (under ``telemetry_enabled``: only
        then does the step report them) from the scalars of EARLIER steps
        the device has finished; a step still running is left for a later
        call, so this never waits.  The last steps of a run stay unread."""
        pending = self._pending_layer_stats
        pending.append({k: metrics[k] for k in _LAYER_STATS if k in metrics})
        r = telemetry.registry()
        while pending and all(v.is_ready() for v in pending[0].values()):
            for key, value in pending.popleft().items():
                kind, name, text = _LAYER_STATS[key]
                if kind == "gauge":
                    r.gauge(name, text).set(float(value))
                else:
                    r.counter(name, text).inc(float(value))

    def eval_loss(self, state: TrainState,
                  batch: typing.Dict[str, jax.Array]
                  ) -> typing.Dict[str, jax.Array]:
        """Forward-only held-out loss/accuracy on one eval batch.

        Deterministic: traced with ``params.train`` False (dropout off, no
        router-aux injection) and no rng, on the same mesh as training — the
        driver metric is tokens/sec/chip + VAL LOSS (BASELINE.json), and this
        is its loss half.  Compiled once; the eval batch must be shaped like
        a train micro batch (no macro axis)."""
        p = self.params
        self._ensure_eval_fn()
        if self.mesh is not None:
            batch = shardlib.shard_batch(p, batch, self.mesh, batch_axis=0)
        return self._eval_fn(state.variables, batch)

    def _ensure_eval_fn(self):
        if self._eval_fn is not None:
            return
        p = self.params

        def eval_fn(variables, batch):
            saved = p.train
            p.train = False  # trace-time flag: dropout/aux-inject off
            try:
                info = self.model.apply(variables, batch, rng=None,
                                        mesh=self.mesh)
            finally:
                p.train = saved
            return _info_metrics(info)
        self._eval_fn = jax.jit(eval_fn)

    def lowered_eval(self, state: TrainState,
                     batch: typing.Dict[str, jax.Array]):
        """Lowered eval fn for the HLO audit (analysis/entry_points.py) —
        the same jit ``eval_loss`` runs, without executing it."""
        self._ensure_eval_fn()
        if self.mesh is not None:
            batch = shardlib.shard_batch(self.params, batch, self.mesh,
                                         batch_axis=0)
        return self._eval_fn.lower(state.variables, batch)

    def moe_stats(self, state: TrainState, batch: typing.Dict[str, jax.Array],
                  rng: typing.Optional[jax.Array] = None
                  ) -> typing.Dict[str, typing.Dict[str, jax.Array]]:
        """Per-layer MoE routing statistics: {scope_path: {stat: value}} with
        expert utilization (1.0 = balanced), dropped-token fraction, and the
        balance/z-loss values (observable here because the training step only
        injects their GRADIENTS — model/basic.py:_router_aux_inject).

        Runs a forward-only probe whose block recurrence is the strategy-
        faithful python loop (identical activations to the trained forward;
        run_body_blocks' stats path) so layer stats can legally flow out of
        the trace.  Compiled once; intended for every-N-steps monitoring
        (config ``moe_metrics_interval``)."""
        p = self.params
        if rng is None:
            rng = jax.random.PRNGKey(p.current_step)
        if self._stats_fn is None:
            def stats_fn(variables, batch, rng):
                if p.macro_batching > 1:  # probe the first micro slice
                    batch = {k: v[0] for k, v in batch.items()}
                sink: list = []
                self.model.apply(variables, batch, rng, mesh=self.mesh,
                                 stats_sink=sink)
                out: typing.Dict[str, dict] = {}
                for path, stats in sink:
                    key = path if path not in out else f"{path}#{len(out)}"
                    out[key] = stats
                return out
            self._stats_fn = jax.jit(stats_fn)
        if self.mesh is not None and not self._batch_placed(batch):
            batch = shardlib.shard_batch(p, batch, self.mesh)
        return jax.device_get(self._stats_fn(state.variables, batch, rng))
