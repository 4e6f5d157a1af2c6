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
import functools
import typing

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..config import ModelParameter
from ..core import sharding as shardlib
from ..model import Model, declare
from ..model.remat import stash_line, stash_plan
from ..optim import Optimizer, own_rule
from ..optim.gradients import MULTI_LOSS_GRADIENTS
from ..telemetry import memory

Params = typing.Dict[str, jax.Array]


def _info_metrics(info) -> typing.Dict[str, jax.Array]:
    """Loss/accuracy metrics from a model BuildInfo (None -> 0), and what
    its layers reported of themselves (``LossInfo.layer_stats``), each
    statistic folded over the layers as its layer declares
    (model/declare.py ``Stat``)."""
    return {
        **declare.fold_stats(getattr(info, "layer_stats", None)),
        "loss": info.total_loss.data.astype(jnp.float32),
        "token_loss": (info.token_loss.data.astype(jnp.float32)
                       if info.token_loss is not None else jnp.float32(0)),
        "video_loss": (info.video_loss.data.astype(jnp.float32)
                       if info.video_loss is not None else jnp.float32(0)),
        "accuracy": (info.accuracy.data.astype(jnp.float32)
                     if info.accuracy is not None else jnp.float32(0)),
    }


def _grad_norm_metrics(grads: Params, debug: bool) -> typing.Dict[str, jax.Array]:
    # a leaf with a rule of its own (optim/__init__.py) holds no gradient
    grads = {k: g for k, g in grads.items() if not own_rule(k)}
    extra = {}
    if debug:
        # per-variable gradient norms (the reference's --debug_grad
        # histogram summaries, src/run/run.py:147-153)
        extra = {f"grad_norm/{k}": jnp.sqrt(jnp.sum(g.astype(jnp.float32) ** 2))
                 for k, g in grads.items()}
    extra["global_grad_norm"] = jnp.sqrt(sum(
        jnp.sum(g.astype(jnp.float32) ** 2) for g in grads.values()))
    return extra


#: ``{step metric: its declaration}`` (kind, metric name, help text) of what
#: the layers report of themselves: what ``Trainer._publish_layer_stats``
#: publishes
_LAYER_STATS = declare.stats()



def _stat_of(metric: str) -> str:
    """The declared statistic behind a step metric: itself, or what stands
    before the ``/<index>`` of a labelled one."""
    return metric.partition("/")[0]


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
        # one entry a call of ``step``, always on (telemetry/step_clock.py):
        # the listener of this trainer's span sites and, as the trainer
        # built last, of the prefetcher's ``data/next`` and the collector
        self.step_clock = telemetry.step_clock.install(
            telemetry.StepClock(record=self._record_steps))
        # the chip's memory (telemetry/memory.py): the start-up line of
        # init_state's marks, and the one ``step`` leaves here at its mark
        # (None until then, and on a backend that reports nothing);
        # ``train()`` prints both.  ``_step_loaded``: that mark is made
        self.state_memory_line: str = memory.NOT_REPORTED
        self.step_memory_line: typing.Optional[str] = None
        self._step_loaded = False

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
        return None

    def _grads(self, variables: Params, batch, rng):
        p = self.params
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
            info = self.model.apply(v, batch, rng, mesh=mesh,
                                    layer_stats=self._record_steps)
            # what the step differentiates: the reported loss, or where a
            # multi-token-prediction module adds its own at a weight
            # (model/mtp.py) the sum, which the step does not report
            whole = info.total_loss if info.objective is None \
                else info.objective
            return (whole.data if idx is None
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
        grads, info = self._grads(variables, batch, rng)
        base_metrics = _info_metrics(info)
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
        that is not engaged), and the start-up facts the registered layers
        declare of their mechanisms (model/declare.py ``Fact``: a gauge each
        and its part of the line).  Set when the step is built; returns the
        start-up line that says the same."""
        plan = stash_plan(self.params, self.mesh)
        line = stash_line(plan, self.params.loop_steps > 1)
        r = telemetry.registry()
        for fact in declare.facts():
            value = fact.value(self.params, self.mesh, None)
            if fact.label and value is not None:
                gauge = r.gauge(fact.metric, fact.help, (fact.label,))
                for key, number in value.items():
                    gauge.labels(key).set(number)
                value = " ".join(f"{key} {number:.6g}"
                                 for key, number in value.items())
            elif value is not None or fact.zero:
                r.gauge(fact.metric, fact.help).set(value or 0)
            if value is not None:
                line += "; " + fact.fragment.format(value)
        nbytes = r.gauge("hbnlp_remat_stash_bytes",
                         "per-device bytes riding the memory strategy's "
                         "residuals instead of being replayed", ("kind",))
        nlayers = r.gauge("hbnlp_remat_stash_layers",
                          "layer outputs riding the memory strategy's "
                          "residuals, one each time the step runs the layer "
                          "(a looped model: every pass)", ("kind",))
        for kind, (layers, size) in plan.items():
            nbytes.labels(kind).set(size)
            nlayers.labels(kind).set(layers)
        return line

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
        with telemetry.span("data/place", record=self._record_steps,
                            listener=self.step_clock):
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
        # step() has it.  Its annotation carries the step's number in the
        # step clock's ring, which joins a captured window's executions of
        # the step to their entries
        clock = self.step_clock
        with telemetry.span("train/step_dispatch", record=self._record_steps,
                            listener=clock, step=clock.steps):
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
            clock.dispatched(metrics["loss"])
            if not self._step_loaded and clock.completed:
                self._mark_step_loaded()
            if any(_stat_of(k) in _LAYER_STATS for k in metrics):
                self._publish_layer_stats(metrics)
            return state, metrics

    def _mark_step_loaded(self) -> None:
        """Point ``step_loaded`` of telemetry/memory.py, at the first call
        whose enter the step clock found the FIRST step (the one that
        traced, compiled and loaded the program) done: the program has run
        once, so the runtime's reservation is the step's scratch and
        ``in_use`` what the loop keeps.  The clock's poll never waits, and
        this reads its ring and polls nothing itself; once made, a step
        pays one ``not``."""
        self._step_loaded = True
        self.step_memory_line = memory.loaded_line(memory.mark("step_loaded"))

    def _publish_layer_stats(self, metrics) -> None:
        """The statistics the layers declare (``_LAYER_STATS``; under
        ``telemetry_enabled``: only then does the step report them) from the
        scalars of EARLIER steps the device has finished; a step still
        running is left for a later call, so this never waits.  The last
        steps of a run stay unread."""
        pending = self._pending_layer_stats
        pending.append({k: v for k, v in metrics.items()
                        if _stat_of(k) in _LAYER_STATS})
        r = telemetry.registry()
        while pending and all(v.is_ready() for v in pending[0].values()):
            for key, value in pending.popleft().items():
                stat = _LAYER_STATS[_stat_of(key)]
                if stat.kind == "counter":
                    r.counter(stat.metric, stat.help).inc(float(value))
                elif stat.label:
                    r.gauge(stat.metric, stat.help, (stat.label,)).labels(
                        key.partition("/")[2]).set(float(value))
                else:
                    r.gauge(stat.metric, stat.help).set(float(value))

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
