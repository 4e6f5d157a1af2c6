"""Training run loop.

Replaces the reference's MonitoredTrainingSession stepping
(/root/reference/src/run/run.py:220-262).  Differences by design:
data decode runs in a background prefetcher overlapping the device step (the
reference serialized infeed after compute, run.py:251-256), checkpoints are
the in-tree sharded format, and metrics go to TensorBoard-compatible event
files without TF.
"""
from __future__ import annotations

import json
import os
import signal
import sys
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..config import ModelParameter
from ..core import sharding as shardlib
from ..data.inputs import (Prefetcher, TextDataset, append_runs_log,
                           read_runs_log)
from ..telemetry import events as flight
from ..model import Model
from ..train import Trainer
from ..train import checkpoint as ckpt
from ..train.metrics import MetricLogger
from ..utils import fs
from ..utils import retry as retry_mod
from .analysis import analyze_model

#: exit code of a run that stopped on SIGTERM/SIGINT after writing its
#: emergency checkpoint — resumable, not a crash.  143 = 128+SIGTERM, what an
#: unhandled TERM would have produced, so generic supervisors treat it the
#: same; scripts/run_manager.py recognises it and relaunches instead of
#: declaring the run finished (keep the two constants in sync).
PREEMPTED_EXIT_CODE = 143

#: exit code of a run that stopped because pod MEMBERSHIP changed (a peer's
#: lease lapsed): unlike 143 no emergency checkpoint is possible (the pod
#: lost a rank mid-step, distributed-save barriers would hang on it), so
#: the elastic controller resumes the surviving hosts from the freshest
#: COMPLETE checkpoint.  One definition, in the elastic module.
from ..distributed.elastic import MEMBERSHIP_EXIT_CODE  # noqa: E402


class NonFiniteLossError(RuntimeError):
    """``nonfinite_loss_tolerance`` consecutive non-finite losses: the run
    aborts (after the finally-path emergency checkpoint of the last GOOD
    state) instead of training on poisoned weights."""


class _ShutdownFlag:
    """SIGTERM/SIGINT handler: request a graceful stop.  The loop finishes
    the in-flight step, then the finally path writes the emergency
    checkpoint and rewrites the run log — the run exits resumable.

    Reused by the serving path (run/modes.py web_api_mode) with a custom
    ``message`` and an ``on_signal`` callback (an Event's ``set``), so the
    second-signal force-exit and reentrancy-safe write protocol live in ONE
    place."""

    def __init__(self, message: typing.Optional[str] = None,
                 on_signal: typing.Optional[typing.Callable[[], None]] = None):
        self.requested = False
        self.signum: typing.Optional[int] = None
        self.message = message or ("finishing the in-flight step, then "
                                   "writing an emergency checkpoint "
                                   "(repeat to force-exit)")
        self.on_signal = on_signal

    def __call__(self, signum, frame):
        if self.requested:
            # second signal: the operator insists (e.g. the emergency save
            # is itself hung on storage retries) — restore the default
            # disposition and re-deliver so the process actually dies
            signal.signal(signum, signal.SIG_DFL)
            signal.raise_signal(signum)
            return
        self.requested = True
        self.signum = signum
        if self.on_signal is not None:
            self.on_signal()
        # os.write, not print: a signal landing mid-print would make
        # buffered stdout raise "reentrant call" in the main thread, turning
        # the graceful path into a crash
        try:
            os.write(2, (f"received {signal.Signals(signum).name}: "
                         f"{self.message}\n").encode())
        except OSError:
            pass


def _dump_run_config(params: ModelParameter):
    fs.makedirs(params.model_path)
    # epoch filename stamp, not a duration  # graft-lint: allow[wallclock]
    path = fs.join(params.model_path, f"run_config_{int(time.time())}.json")
    safe = {}
    for k, v in params.dict().items():
        try:
            json.dumps(v)
            safe[k] = v
        except TypeError:
            safe[k] = str(v)
    with fs.open_(path, "w") as f:
        json.dump(safe, f, indent=2)


def _macro_batches(dataset, macro: int):
    """Group per-step sub-batches into [macro, batch, ...] arrays."""
    it = iter(dataset)
    while True:
        group = []
        try:
            for _ in range(macro):
                group.append(next(it))
        except StopIteration:
            return
        if macro == 1:
            yield group[0]
        else:
            yield {k: np.stack([g[k] for g in group]) for k in group[0]}


class _AsyncFeeder:
    """Double-buffered host->device input transfer (``async_input_transfer``,
    docs/PERFORMANCE.md 'Round 11').

    The historical loop ordering was fetch -> transfer -> dispatch: the
    next batch's host->device copy only STARTED after the previous step's
    dispatch returned, serialized against device compute.  This iterator keeps
    ONE batch in flight: each ``__next__`` returns the batch whose
    transfer was already started on the PREVIOUS call, then immediately
    starts the next one via ``Trainer.place_batch`` (``jax.device_put`` /
    sharded placement — asynchronous on real accelerators), so the copy
    overlaps the device step dispatched right after.  One extra device
    batch stays resident; batches are never donated, so there is no
    aliasing hazard."""

    def __init__(self, it, place):
        self._it = iter(it)
        self._place = place
        self._pending = None
        self._raised: typing.Optional[BaseException] = None

    def __iter__(self):
        return self

    def __next__(self):
        if self._pending is None:
            if self._raised is not None:
                raise self._raised
            self._pending = self._place(next(self._it))
        out = self._pending
        self._pending = None
        try:
            self._pending = self._place(next(self._it))
        except BaseException as exc:  # noqa: BLE001 — deferred, not hidden
            # the CURRENT batch is still valid: hand it out and re-raise
            # on the NEXT call — StopIteration (normal exhaustion) and
            # real pipeline errors alike must not cost the step whose
            # transfer already completed (the historical ordering would
            # have run that step before ever seeing the failure)
            self._raised = exc
        return out


def data_slice_geometry(mesh=None):
    """The (slice_index, slice_count) the dataset actually feeds with: the
    data-axis process groups (full model parallelism replicates identical
    batches per group), not the raw process count.  The run log must record
    THIS slice_count — the resume replay is keyed on it."""
    nproc = max(1, jax.process_count())
    if mesh is not None and nproc > 1:
        return shardlib.process_data_slice(mesh)
    return jax.process_index(), nproc


def make_dataset(params: ModelParameter, repeat: bool = True, mesh=None):
    # use_random_dataloader: randomized debug pipeline — no deterministic
    # resume (reference dataloader_placement.py:121,155)
    runs_log = [] if params.use_random_dataloader else read_runs_log(params)
    # each process loads only its slice of the global batch; shard_batch
    # assembles the slices via make_array_from_process_local_data
    slice_index, slice_count = data_slice_geometry(mesh)
    if params.use_random_dataloader and slice_count < max(1, jax.process_count()):
        # several processes feed the SAME batch slice (full model
        # parallelism): each process's unseeded shuffle would order windows
        # differently and the assembled global batch would mix them —
        # duplicated and dropped windows with no error
        raise ValueError("use_random_dataloader requires per-process data "
                         "slices; this layout replicates batches across "
                         "processes, which an unseeded shuffle would desync")
    if params.train_batch_size % slice_count:
        raise ValueError(f"train_batch_size {params.train_batch_size} must "
                         f"divide evenly over {slice_count} batch slices")
    if params.use_video:
        # jannet mode: weighted video/text mixing (reference dataset(),
        # inputs.py:486-525) — frames + tokens + masks per batch.  Resume
        # follows the reference's video semantics: skip the already-consumed
        # sub-batches (dataset.skip(current_step), dataloader_placement.py:
        # 155-156) instead of the text path's run-log replay
        import itertools
        from ..data.video import mixed_dataset
        dataset: typing.Iterable = mixed_dataset(
            params, params.train_batch_size // slice_count,
            slice_index=slice_index, slice_count=slice_count, repeat=repeat)
        if params.current_step and not params.use_random_dataloader:
            # sub-batches consumed == step counter: each macro-group consumes
            # macro_batching sub-batches AND advances the step by the same
            dataset = itertools.islice(dataset, params.current_step, None)
    else:
        # eval_holdout_files: the last N files of every glob are reserved
        # for the eval pass and never trained on (data/inputs.py)
        holdout = (("train", params.eval_holdout_files)
                   if params.eval_holdout_files else None)
        dataset = TextDataset(params, params.train_batch_size // slice_count,
                              slice_index=slice_index,
                              slice_count=slice_count,
                              runs_log=runs_log or None, repeat=repeat,
                              holdout=holdout)
    return Prefetcher(_macro_batches(dataset, params.macro_batching),
                      depth=params.buffer_size,
                      telemetry_label="train" if params.telemetry_enabled
                      else None)


def make_eval_batches(params: ModelParameter, mesh=None
                      ) -> typing.List[typing.Dict[str, np.ndarray]]:
    """The FIXED held-out eval set: ``eval_steps`` micro batches, same every
    eval so val loss is comparable across steps and runs.  Sources
    ``eval_dataset_configs`` when given, else the ``eval_holdout_files``
    tail of the training globs; same per-process slice geometry as
    training."""
    import itertools
    slice_index, slice_count = data_slice_geometry(mesh)
    cfgs = params.eval_dataset_configs or None
    if cfgs is None and not params.eval_holdout_files:
        raise ValueError("eval_interval > 0 needs eval_dataset_configs or "
                         "eval_holdout_files > 0")
    holdout = (("eval", params.eval_holdout_files) if cfgs is None else None)
    ds = TextDataset(params, params.train_batch_size // slice_count,
                     slice_index=slice_index, slice_count=slice_count,
                     runs_log=None, repeat=True, dataset_configs=cfgs,
                     holdout=holdout)
    batches = list(itertools.islice(iter(ds), params.eval_steps))
    if not batches:
        raise ValueError("eval dataset produced no batches")
    return batches


def train(params: ModelParameter, train_steps: typing.Optional[int] = None,
          log_every: int = 10,
          profile_steps: typing.Optional[typing.Tuple[int, int]] = None
          ) -> typing.Dict[str, typing.Any]:
    """profile_steps=(start, stop): capture a jax.profiler trace of those
    steps into <model_path>/profile (SURVEY.md §5.1 — the reference had no
    op-level profiler integration)."""
    # transient-storage retry budget for this run's checkpoint/GCS traffic
    # (utils/retry.py; every fs call site in train/checkpoint.py + every
    # GCSFS primitive reads this policy at call time)
    retry_mod.set_default_policy(retry_mod.RetryPolicy(
        max_attempts=params.storage_retry_attempts,
        base_delay=params.storage_retry_base_delay))
    t_entry = time.monotonic()
    devices = jax.devices()
    from ..utils import flops as flops_mod
    print(flops_mod.describe_devices(), flush=True)
    mesh = shardlib.build_mesh(params) if len(devices) > 1 else None
    model = Model(params)
    trainer = Trainer(params, model, mesh=mesh)
    # host-side artifacts (run config, model_size.info, DataLog, metrics,
    # checkpoints) are written by the chief only: on a multi-host pod every
    # process runs this loop against one shared model_path (the reference
    # wrote these to GCS the same way)
    is_chief = jax.process_index() == 0
    if is_chief:
        _dump_run_config(params)

    # ---- flight recorder (docs/OBSERVABILITY.md 'Flight recorder'):
    # typed rare events into a bounded ring, dumped as
    # <model_path>/blackbox_p<rank>.jsonl on every exit path.  Recording is
    # UNCONDITIONAL (independent of telemetry_enabled) but never touches
    # the registry and never runs per step — step records ride the
    # metric-log cadence, everything else is genuinely rare.
    from ..distributed.elastic import generation as _elastic_generation
    flight.configure(params.model_path, f"p{jax.process_index()}",
                     capacity=params.telemetry_blackbox_events)

    # async checkpointing (docs/DISTRIBUTED.md): cadence + emergency saves
    # go through the double-buffered background saver — the step thread pays
    # only the device->host staging copy.  Every process routes through the
    # SAME path (the distributed write protocol assigns writer roles).
    saver = None
    if params.use_checkpointing and params.checkpoint_async:
        from ..distributed.async_checkpoint import AsyncCheckpointer
        saver = AsyncCheckpointer(params.distributed_barrier_timeout_s)

    def save_state(at_step: int) -> None:
        if saver is not None:
            saver.submit(params.model_path, at_step, state.variables,
                         state.opt_state, params.max_checkpoints_keep)
        else:
            ckpt.save(params.model_path, at_step, state.variables,
                      state.opt_state, params.max_checkpoints_keep)

    # restore through the corruption fallback: a torn/corrupt latest
    # checkpoint costs one checkpoint interval, not the run; strict = an
    # all-corrupt model_path refuses to train from scratch over the corpse
    restored = ckpt.restore_latest_valid(params.model_path, strict=True) \
        if params.use_checkpointing else None
    if params.use_checkpointing and jax.process_count() > 1:
        # all hosts must resume from the SAME step: a host whose torn read
        # made it fall back further than its peers would desync current_step
        # and deadlock the step-tagged barriers of the distributed save.
        # The chief's choice wins (its fallback warnings are the visible
        # ones); hosts re-restore when they disagree.
        local_step = restored[2] if restored else -1
        try:
            from jax.experimental import multihost_utils
            agreed = int(multihost_utils.broadcast_one_to_all(
                np.asarray(local_step, np.int32)))
        except Exception:
            agreed = local_step  # no cross-host collectives (CPU tests)
        if agreed != local_step:
            restored = ckpt.restore(params.model_path, agreed) \
                if agreed >= 0 else None
    params.current_step = restored[2] if restored else ckpt.latest_step(params.model_path)
    flight.record("run_start", rank=jax.process_index(),
                  world=jax.process_count(), gen=_elastic_generation(),
                  step=int(params.current_step))
    if restored:
        flight.record("restore", step=int(restored[2]))

    data = make_dataset(params, mesh=mesh)
    if not params.use_video:
        from ..data import native_recordio
        print(f"record reader: {native_recordio.describe()}", flush=True)
    first_batch = next(iter(data))
    state = trainer.init_state(first_batch)
    if restored:
        variables, opt_state, step, _ = restored
        variables = {k: np.asarray(v).astype(state.variables[k].dtype)
                     for k, v in variables.items()}
        from ..train import TrainState
        # the freshly-initialised state is the sharding template: place_tree
        # lays every restored host array out identically (including
        # optimizer slots, and including cross-process shardings where a
        # bare device_put cannot reach non-addressable devices)
        state = TrainState(
            shardlib.place_tree(state.variables, variables),
            shardlib.place_tree(state.opt_state, opt_state),
            shardlib.place_tree(state.step, np.asarray(step, np.int32)))
        print(f"restored checkpoint at step {step}")
    print(shardlib.placement_report(state.variables, mesh), flush=True)
    print(trainer.publish_stash_plan(), flush=True)
    print(trainer.state_memory_line, flush=True)

    compile_s = 0.0
    if is_chief:
        # analyze_model reads shapes only — no device_get (which would also
        # fail on non-fully-addressable arrays in multi-host model sharding)
        analyze_model(params, state.variables, model.param_dims)
        if not params.use_random_dataloader:
            # a shuffled run consumes windows out of order: logging it would
            # poison a later deterministic run's skip replay
            append_runs_log(params, 0, data_slice_geometry(mesh)[1])
        if params.save_graph:
            # reference saved the TF graph_def with checkpoints
            # (run.py:171); the XLA-native artifacts are the lowered step
            # and the executable XLA makes of it.  The compile is not paid
            # twice: the step's own jit finds it in the persistent cache
            lowered = trainer.lowered(state, first_batch)
            path = fs.join(params.model_path, "train_step.stablehlo.txt")
            with fs.open_(path, "w") as f:
                f.write(lowered.as_text())
            t0 = time.monotonic()
            hlo = lowered.compile().as_text()
            compile_s = time.monotonic() - t0
            hlo_path = fs.join(params.model_path, "train_step.hlo.txt")
            with fs.open_(hlo_path, "w") as f:
                f.write(hlo)
            from ..analysis import hlo_lint
            print(f"save_graph: lowered train step written to {path}, "
                  f"compiled ({compile_s:.1f}s) to {hlo_path}; kernels in "
                  f"the executable: {hlo_lint.custom_call_census(hlo)}",
                  flush=True)

    # ---- elastic membership (docs/DISTRIBUTED.md 'Elasticity'): a daemon
    # thread heartbeats a lease in the coordination KV and scans its peers;
    # a lapsed peer makes every survivor exit MEMBERSHIP_EXIT_CODE so the
    # elastic controller re-forms the pod at the surviving world size.  The
    # chief's pre-exit hook flushes the DataLog consumption count even on
    # the force-exit path (os._exit skips every finally), keeping the
    # data-stream resume multiset-exact across the membership change.
    elastic_agent = None
    datalog_flush = None
    consumed_ref = [0]
    if is_chief and not params.use_random_dataloader:
        import threading as _threading

        from ..utils import locks as _locks
        _flush_lock = _locks.named_lock("train_loop._flush_lock")
        _flushed = [False]

        def datalog_flush(final: bool = False):
            """Rewrite the run-log entry with the sub-batches actually
            consumed — the ONE copy both the plain finally path and (when
            elastic) the agent's force-exit hook route through.
            Once-locked: a force-exit racing the finally must not tear
            the log mid-rewrite; the FIRST writer wins."""
            with _flush_lock:
                if _flushed[0] and not final:
                    return
                _flushed[0] = True
                log = read_runs_log(params)
                if log:
                    log[-1]["steps"] = consumed_ref[0]
                    # IO under the lock is the POINT here: the first
                    # writer must finish the rewrite before a racing
                    # force-exit path starts  # graft-lint: allow[lock-blocking]
                    with fs.open_(fs.join(params.model_path,
                                          "DataLog.log"), "w") as f:
                        for entry in log:
                            f.write(json.dumps(entry) + "\n")

    # host-side step mirror for the lease heartbeat + straggler detector:
    # a plain list-cell assignment per loop turn, never a registry call —
    # the zero-call hot-path contract is untouched
    progress_ref = [int(params.current_step)]
    #: telemetry-gated straggler counter, bound later (the registry block
    #: below runs after the agent starts); the agent's callback reads the
    #: cell at flag time
    straggler_counter: typing.List[typing.Any] = [None]

    def _force_exit_flush():
        """What ``os._exit`` would lose, for the agent's force-exit hook
        (the finally path never runs there): the chief's DataLog rewrite.
        The blackbox itself is flushed by the agent AFTER this hook."""
        if datalog_flush is not None:
            datalog_flush()

    if params.elastic_training and jax.process_count() > 1:
        from ..distributed.elastic import ElasticAgent

        def _on_straggler(rank, stall_s, median_s):
            counter = straggler_counter[0]
            if counter is not None:
                counter.inc()

        elastic_agent = ElasticAgent(
            params.model_path, jax.process_index(), jax.process_count(),
            interval_s=params.elastic_lease_interval_s,
            timeout_s=params.elastic_lease_timeout_s,
            exit_grace_s=params.elastic_exit_grace_s,
            pre_exit=_force_exit_flush,
            progress=lambda: progress_ref[0],
            straggler_factor=params.elastic_straggler_factor,
            on_straggler=_on_straggler).start()
        print(f"elastic: lease agent started (generation "
              f"{elastic_agent.gen}, world size {jax.process_count()}, "
              f"interval {params.elastic_lease_interval_s}s, timeout "
              f"{params.elastic_lease_timeout_s}s)", flush=True)

    eval_batches = None
    if params.eval_interval:
        if params.use_video:
            print("WARNING: eval_interval is text-only; no val loss for "
                  "video runs")
        else:
            eval_batches = make_eval_batches(params, mesh=mesh)

    logger = MetricLogger(params.model_path) if is_chief else None
    if logger is not None and params.use_random_dataloader:
        # the auto-generated data_seed (config.py) must outlive the console:
        # a metrics.jsonl note makes the run reproducible after the fact
        logger.note(data_seed=int(params.data_seed),
                    data_seed_auto_generated=True)
    # ---- telemetry (docs/OBSERVABILITY.md): everything below is created
    # ONCE, outside the loop; when telemetry_enabled is false every handle
    # stays None and the step loop makes exactly zero registry calls (the
    # per-step spans live where the work is: Trainer.step, place_batch and
    # the prefetcher's __next__, gated the same way)
    tel_nonfinite = tel_preempt = None
    tel_jsonl = None
    tel_jsonl_last = [0.0]
    tel_publish = tel_gather = None
    tel_tokens = None
    tel_membership = None
    if params.telemetry_enabled:
        telemetry.register_build_info()
        if jax.process_count() > 1:
            # every exported series names the host it came from; the chief's
            # cross-host merge then unions per-process series instead of
            # summing different hosts into anonymity (docs/DISTRIBUTED.md)
            telemetry.set_constant_labels(
                {"process": str(jax.process_index())})
        reg = telemetry.registry()
        tel_nonfinite = reg.counter(
            "hbnlp_train_nonfinite_skips_total",
            "steps whose update was skipped on a non-finite loss")
        tel_preempt = reg.counter(
            "hbnlp_train_preemptions_total",
            "graceful SIGTERM/SIGINT stops (emergency checkpoint written)")
        if elastic_agent is not None:
            # elastic observability (docs/DISTRIBUTED.md 'Elasticity'):
            # which generation this process believes it is in, at what
            # world size, and how many membership exits it has taken —
            # the controller-side run.log and these series must agree
            reg.gauge(
                "hbnlp_elastic_generation",
                "fleet generation this process launched under "
                "(HBNLP_GENERATION, stamped by the elastic controller)"
            ).set(elastic_agent.gen)
            reg.gauge(
                "hbnlp_elastic_world_size",
                "process count of this generation's jax cluster"
            ).set(jax.process_count())
            tel_membership = reg.counter(
                "hbnlp_elastic_membership_exits_total",
                "membership-change exits (peer lease lapse or coordinator "
                "loss; resumed by the elastic controller from the freshest "
                "complete checkpoint)")
            if params.elastic_straggler_factor > 0:
                straggler_counter[0] = reg.counter(
                    "hbnlp_elastic_straggler_flags_total",
                    "slow-but-alive ranks flagged by the chief's straggler "
                    "detector (step-time skew vs fleet median, before the "
                    "lease lapses)")
        # chief-only: tokens_per_step is a GLOBAL quantity — every host
        # registering it would make a cross-host merge (or a per-host scrape
        # summed downstream) report N× the real token rate.  A rate over
        # this counter is the operator's throughput; there is no per-step
        # utilization gauge, because a step's time is only known to the host
        # through a device sync, and measuring must not change what is
        # measured
        if is_chief:
            tel_tokens = reg.counter(
                "hbnlp_train_tokens_total",
                "tokens fed to the device (rate() of this is tokens/sec)")
        if is_chief and params.telemetry_jsonl_interval_s > 0:
            # size-capped rotation (telemetry_max_file_mb, keep-last-N):
            # a long run's trajectory can no longer fill the disk.  The
            # header line — rewritten into every rotated generation — joins
            # each file back to the build that produced it
            tel_jsonl = telemetry.RotatingJsonl(
                fs.join(params.model_path, "telemetry.jsonl"),
                max_mb=params.telemetry_max_file_mb,
                keep=params.telemetry_keep_files,
                header=json.dumps({"build_info": telemetry.build_info()}))
            tel_jsonl.flush()
        # cross-host merge (docs/DISTRIBUTED.md): non-chief hosts publish
        # their (process-labeled) snapshots over the coordination KV store
        # at the jsonl cadence; the chief merges the freshest peer snapshots
        # with its own into ONE telemetry.jsonl.  Counters/histograms keep
        # per-process series (the label makes them distinct), gauges stay
        # per-host truth.  No device collectives anywhere on this path.
        if jax.process_count() > 1 and params.telemetry_jsonl_interval_s > 0:
            import base64
            import pickle
            from .. import distributed as dist_mod
            if not is_chief:
                def tel_publish():
                    dist_mod.kv_put(
                        f"hbnlp/telemetry/p{jax.process_index()}",
                        base64.b64encode(
                            pickle.dumps(telemetry.snapshot())).decode())
            else:
                def tel_gather():
                    peers = []
                    for _, val in dist_mod.kv_dir_get("hbnlp/telemetry/"):
                        try:
                            peers.append(pickle.loads(
                                base64.b64decode(val.encode())))
                        except Exception:
                            pass  # torn publish: skip this peer this tick
                    snap = telemetry.snapshot()
                    return telemetry.merge_snapshots(*peers, snap) \
                        if peers else snap
    # on-demand XLA profiling is independent of telemetry_enabled: it has
    # zero per-step cost until a SIGUSR2 actually requests a capture
    profiler_od = None
    if params.telemetry_profile_on_signal:
        from ..telemetry import OnDemandProfiler
        profiler_od = OnDemandProfiler(
            os.path.join(params.model_path, "profile"),
            params.telemetry_profile_steps)
        profiler_od.install_signal()
    # SIGUSR2 also dumps the blackbox on demand; installed AFTER the
    # profiler so the chained handler serves both (flush, then delegate) —
    # and uninstalled FIRST on the way out (LIFO, before profiler close)
    flight_unsig = flight.recorder().install_signal()
    total_steps = train_steps if train_steps is not None else params.train_steps
    tokens_per_step = (params.train_batch_size * params.sequence_length
                       * params.macro_batching)
    start_step = int(state.step)
    steps_done = 0
    # sub-batches actually fed to the device, INCLUDING non-finite-skipped
    # steps (their batches are consumed without an update): the DataLog
    # resume replay must skip exactly this many, or a resumed run would
    # re-feed the skipped batches and shift every later one
    consumed = 0
    it_count = 0
    last_metrics: typing.Dict[str, float] = {}
    t_start = time.monotonic()
    # preemption-safe shutdown: TPU preemptions deliver SIGTERM; finish the
    # in-flight step, write the emergency checkpoint (finally path), exit
    # resumable.  Previous handlers are restored on the way out; outside the
    # main thread (no signal access) training simply runs unguarded.
    shutdown = _ShutdownFlag()
    prev_handlers: typing.Dict[int, typing.Any] = {}
    try:
        for sig in (signal.SIGTERM, signal.SIGINT):
            prev_handlers[sig] = signal.signal(sig, shutdown)
    except ValueError:
        prev_handlers = {}
    nonfinite_streak = 0
    stopped = False
    membership = False
    nproc = jax.process_count()
    broadcast_ok = [True]
    # pods agree on the stop at this iteration cadence: a blocking broadcast
    # EVERY iteration would serialise host dispatch against compute (the
    # same per-step-sync trap the step_now mirror avoids); every 16th costs
    # ~nothing and delays a graceful stop by at most 16 steps of the
    # preemption grace window
    stop_sync_every = 16

    def should_stop(it: int) -> bool:
        """Pod-wide agreement on the graceful stop.  Hosts receive SIGTERM
        at different loop ticks; if each broke at its own step, the peers'
        in-flight step collectives and the step-tagged barriers of the
        distributed emergency save would never match — a silent deadlock in
        exactly the preemption window this path exists for.  The chief's
        flag decides for everyone, checked on a deterministic iteration
        cadence identical across hosts (free single-process)."""
        if nproc <= 1 or not broadcast_ok[0]:
            return shutdown.requested
        if it % stop_sync_every:
            # between agreement points a pod host must NOT act on its local
            # flag: breaking alone is exactly the deadlock being prevented
            return False
        try:
            from jax.experimental import multihost_utils
            return bool(multihost_utils.broadcast_one_to_all(
                np.asarray(shutdown.requested)))
        except Exception:
            # multiprocess CPU (the test topology) has no cross-host
            # collectives: fall back to the per-process flag — symmetric
            # across hosts, probed once
            broadcast_ok[0] = False
            return shutdown.requested

    mono = time.monotonic
    setup_s = mono() - t_entry - compile_s
    try:
        batch = first_batch
        data_it = iter(data)
        if params.async_input_transfer:
            # overlap the next batch's device transfer with the running
            # step (docs/PERFORMANCE.md 'Round 11'); the first batch was
            # already consumed above, so the feeder wraps the remainder
            data_it = _AsyncFeeder(data_it, trainer.place_batch)

        profiling = False
        # host-side step mirror: never block on state.step (a device sync per
        # step would serialise dispatch against compute)
        step_now = start_step
        while step_now < total_steps:
            if elastic_agent is not None and \
                    elastic_agent.membership_event() is not None:
                # the clean half of the membership exit: the agent detected
                # a lapsed peer while this thread was BETWEEN steps.  No
                # emergency checkpoint (its barriers would hang on the dead
                # rank); the freshest complete checkpoint is the recovery
                # point.  A thread wedged IN a step never reaches here —
                # the agent's grace-then-force-exit covers that path.
                membership = True
                break
            if profile_steps is not None:
                if not profiling and step_now >= profile_steps[0]:
                    telemetry.start_capture(os.path.join(params.model_path,
                                                         "profile"))
                    profiling = True
                elif profiling and step_now >= profile_steps[1]:
                    jax.profiler.stop_trace()
                    profiling = False
                    # one window: left set, the next turn would find
                    # "not profiling and past the start" and capture again
                    profile_steps = None
            if profiler_od is not None:
                profiler_od.poll(step_now)
            it_count += 1
            # ENTRY semantics for the straggler detector: publish the step
            # being ATTEMPTED before dispatching it.  Completion-based
            # progress equalizes under synchronous collectives (every
            # rank's dispatch blocks on the fleet), so the discriminating
            # signal is the rank that never ARRIVED at the step its peers
            # already entered — the classic barrier-arrival skew
            progress_ref[0] = step_now + params.macro_batching
            t0 = mono()
            state, metrics = trainer.step(state, batch)
            t1 = mono()
            if it_count == 1:
                # the first call traces and compiles (or reloads the
                # executable from the persistent cache) before it
                # dispatches; later calls only dispatch
                compile_s += t1 - t0
            if trainer.step_memory_line is not None:
                # left once, by the step that found the program loaded
                print(trainer.step_memory_line, flush=True)
                trainer.step_memory_line = None
            if tel_tokens is not None:
                tel_tokens.inc(tokens_per_step)
            consumed += params.macro_batching
            consumed_ref[0] = consumed
            if params.nonfinite_loss_tolerance > 0:
                # the jitted step already SKIPPED the update on-device for a
                # non-finite loss (train/__init__.py select); here the host
                # mirrors that skip, tracks the consecutive streak, and
                # aborts once it exhausts the tolerance.  Reading the loss
                # costs one device sync per step — documented in CONFIG.md.
                loss_now = float(np.asarray(jax.device_get(metrics["loss"])))
                if not np.isfinite(loss_now):
                    nonfinite_streak += 1
                    if tel_nonfinite is not None:
                        tel_nonfinite.inc()
                    flight.record("nonfinite", step=step_now,
                                  streak=nonfinite_streak)
                    print(f"WARNING: non-finite loss ({loss_now}) at step "
                          f"{step_now}; update skipped "
                          f"({nonfinite_streak}/"
                          f"{params.nonfinite_loss_tolerance} consecutive)",
                          flush=True)
                    if nonfinite_streak >= params.nonfinite_loss_tolerance:
                        raise NonFiniteLossError(
                            f"aborting: {nonfinite_streak} consecutive "
                            f"non-finite losses (last {loss_now}) at step "
                            f"{step_now}; last good state is step "
                            f"{step_now} (emergency checkpoint follows). "
                            "Suspects: learning rate spike, corrupt batch, "
                            "fp16/bf16 overflow")
                    if should_stop(it_count):
                        stopped = True
                        break
                    try:
                        batch = next(data_it)
                    except StopIteration:
                        break
                    continue
                nonfinite_streak = 0
            steps_done += params.macro_batching
            step_now += params.macro_batching
            if params.debug_train_step:
                # reference run.py:252-262 verbose stepping (host-side only;
                # fetching metrics here would force a device sync per step)
                print(f"debug_train_step: dispatched step {step_now}; "
                      f"fetching next batch", flush=True)
            try:
                batch = next(data_it)
            except StopIteration:
                break
            if params.moe_metrics_interval and \
                    step_now % params.moe_metrics_interval < params.macro_batching:
                # forward-only routing probe (Trainer.moe_stats); scalars
                # merge into the step metrics under moe/<layer path>/<stat>
                metrics = dict(metrics)
                for path, stats in trainer.moe_stats(state, batch).items():
                    metrics.update({f"moe/{path}/{s}": v
                                    for s, v in stats.items()
                                    if np.ndim(v) == 0})
            ran_eval = (eval_batches is not None and
                        step_now % params.eval_interval < params.macro_batching)
            if ran_eval:
                with telemetry.span("train/eval",
                                    listener=trainer.step_clock):
                    vals = [jax.device_get(trainer.eval_loss(state, eb))
                            for eb in eval_batches]
                metrics = dict(metrics, **{
                    f"val/{k}": float(np.mean([v[k] for v in vals]))
                    for k in vals[0]})
            # an eval step always reaches the metric log, so every recorded
            # val/loss point lands in metrics.jsonl/TB even off-cadence
            if ran_eval or step_now % log_every < params.macro_batching:
                with telemetry.span("train/metric_log",
                                    listener=trainer.step_clock):
                    # the float conversions wait for the step: the loop's
                    # one device sync, every log_every steps
                    last_metrics = {**last_metrics, **{
                        k: float(v) for k, v in metrics.items()}}
                    if params.telemetry_enabled:
                        telemetry.memory.mark("running")
                # step record at the metric-log cadence (NOT per step —
                # the float conversions above already paid the sync)
                flight.record("step", step=step_now,
                              loss=last_metrics.get("loss"),
                              consumed=consumed)
                if logger is not None:
                    logger.log(step_now, metrics,
                               tokens_per_step=params.train_batch_size * params.sequence_length)
                if (tel_jsonl is not None or tel_publish is not None) and \
                        mono() - tel_jsonl_last[0] >= params.telemetry_jsonl_interval_s:
                    if tel_publish is not None:
                        tel_publish()
                    else:
                        tel_jsonl.write(telemetry.jsonl_line(
                            tel_gather() if tel_gather is not None
                            else telemetry.snapshot(), step=step_now) + "\n")
                        tel_jsonl.flush()
                    tel_jsonl_last[0] = mono()
            # every process participates in a distributed save (the save
            # itself barriers and assigns writer roles); single-process
            # saves are chief-trivially
            if params.use_checkpointing and \
                    step_now % params.steps_per_checkpoint < params.macro_batching:
                with telemetry.span("train/checkpoint_save",
                                    listener=trainer.step_clock):
                    save_state(step_now)
            if should_stop(it_count):
                # graceful preemption: the in-flight step finished; fall
                # through to the finally path's emergency checkpoint + run
                # log rewrite, then report resumable-exit to the caller
                stopped = True
                break
    finally:
        # the graceful handlers stay installed until the END of this block —
        # restoring them first would let a second SIGTERM/SIGINT kill the
        # process mid-emergency-save, losing exactly the checkpoint this
        # path exists to write
        try:
            try:
                if flight_unsig is not None:
                    # LIFO: restore the chained SIGUSR2 handler BEFORE the
                    # profiler's own uninstall (profiler_od.close below),
                    # or its restore would strand our stale chain
                    flight_unsig()
                    flight_unsig = None
                if elastic_agent is not None and not membership \
                        and sys.exc_info()[0] is None:
                    # normal completion / graceful 143: stop the lease
                    # thread BEFORE the final flushes — peers exiting at
                    # their own pace would otherwise look like lapses and
                    # force-exit this process mid-emergency-save.  On the
                    # membership path the agent stays ALIVE on purpose: its
                    # grace-then-force-exit is the watchdog for a finally
                    # that wedges on the dead rank.  Ditto on an EXCEPTION
                    # unwind: a step that raises under elasticity is most
                    # often the collective noticing a dead peer BEFORE this
                    # rank's lease scan does ("Connection closed by peer"
                    # lands within ms, the lapse only after timeout_s) — the
                    # agent must keep publishing this rank's lease so a
                    # survivor that merely crashed on the dead rank's closed
                    # sockets is not counted as a SECOND lost host, and its
                    # force-exit turns a teardown wedge into a clean 144.  A
                    # genuinely local crash observes no event, and the
                    # daemon thread dies with the process.
                    elastic_agent.stop()
                if membership and tel_membership is not None:
                    tel_membership.inc()
                if profile_steps is not None and profiling:
                    jax.profiler.stop_trace()
                if profiler_od is not None:
                    profiler_od.close()
                if stopped and tel_preempt is not None:
                    tel_preempt.inc()
                if logger is not None:
                    # flush the final metrics window BEFORE the emergency
                    # save: the 30s REMOTE_FLUSH_S cadence lost it on every
                    # preemption whenever the save hung or raised (and
                    # close() below never ran when save raised at all)
                    logger.flush()
                if params.use_checkpointing and not membership:
                    # emergency save participates in the async saver's
                    # commit barrier: submit, then FLUSH the in-flight
                    # background save(s) before this process exits — a
                    # preemption must not race a half-committed
                    # distributed checkpoint (docs/DISTRIBUTED.md).  A
                    # held failure from an EARLIER cadence save is logged
                    # and cleared first: it must not abort the one
                    # checkpoint this path exists to write.  A MEMBERSHIP
                    # exit skips all of it: the save barriers would hang
                    # on the dead rank, and the freshest complete
                    # checkpoint is the agreed recovery point.
                    if saver is not None:
                        old_err = saver.take_error()
                        if old_err is not None:
                            print(f"WARNING: earlier background save "
                                  f"failed ({old_err}); attempting the "
                                  "emergency save anyway", flush=True)
                    save_state(int(state.step))
                    if saver is not None:
                        saver.close()
                # rewrite the run log entry with the steps actually
                # consumed (the once-locked flusher; when elastic, the
                # agent's force-exit hook shares it)
                if datalog_flush is not None:
                    datalog_flush(final=True)
            finally:
                # runs even when the emergency save raises — the metrics
                # files must never be the casualty of a storage failure
                if saver is not None and not membership:
                    try:
                        # idempotent: a second close after the happy-path
                        # one above is a no-op; after a raise mid-finally
                        # this is what drains the in-flight save
                        saver.close()
                    except Exception as e:
                        print(f"WARNING: async checkpoint flush failed: {e}",
                              flush=True)
                if logger is not None:
                    logger.close()
                if tel_publish is not None:
                    try:
                        tel_publish()  # peers' final counters for the chief
                    except Exception:
                        pass
                if tel_jsonl is not None:
                    try:
                        tel_jsonl.write(telemetry.jsonl_line(
                            tel_gather() if tel_gather is not None
                            else telemetry.snapshot(), step=step_now) + "\n")
                        tel_jsonl.close()
                    except Exception as e:
                        print(f"WARNING: final telemetry.jsonl write failed:"
                              f" {e}", flush=True)
                # blackbox dump on EVERY exit that reaches this finally:
                # normal completion, the 143 emergency-save path, the
                # clean half of a membership exit, and any crash unwind
                # (the 144 force-exit path flushes via the agent instead)
                try:
                    exc_type = sys.exc_info()[0]
                    why = ("membership" if membership
                           else "preempted" if stopped
                           else "crash" if exc_type is not None else "ok")
                    flight.record(
                        "exit", rank=jax.process_index(),
                        gen=_elastic_generation(),
                        code=(MEMBERSHIP_EXIT_CODE if membership
                              else PREEMPTED_EXIT_CODE if stopped
                              else 1 if exc_type is not None else 0),
                        reason=why, step=progress_ref[0],
                        error=exc_type.__name__ if exc_type else None)
                    flight.flush(reason=why)
                except Exception as e:
                    print(f"WARNING: blackbox exit dump failed: {e}",
                          flush=True)
        finally:
            for sig, handler in prev_handlers.items():
                signal.signal(sig, handler)
    wall = time.monotonic() - t_start
    if stopped:
        print(f"preempted at step {int(state.step)}: emergency checkpoint "
              f"written; exit {PREEMPTED_EXIT_CODE} resumes from here",
              flush=True)
    if membership:
        print(f"membership change at step {step_now}: "
              f"{elastic_agent.event}; exit {MEMBERSHIP_EXIT_CODE} — the "
              "elastic controller resumes the survivors from the freshest "
              "complete checkpoint", flush=True)
    return {"steps": steps_done, "wall_s": wall,
            "setup_s": setup_s, "compile_s": compile_s,
            "final_step": int(state.step),
            "preempted": stopped,
            "membership_change": elastic_agent.event if membership else None,
            "tokens_per_sec": steps_done * params.train_batch_size
            * params.sequence_length / max(wall, 1e-9),
            **{f"final_{k}": v for k, v in last_metrics.items()}}
