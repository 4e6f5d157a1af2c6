"""Traffic kind ``train``: the program's trainer on its record pipeline.

``run/train_loop.train()`` has no hook between its steps, so this driver
composes what ``train()`` composes, in the same order — ``build_mesh``,
``Model``, ``Trainer``, ``make_dataset`` (the real record reader and
prefetch thread), ``init_state`` on the first batch, the double-buffered
``_AsyncFeeder`` when the configuration asks for it, then ``Trainer.step``
per batch — and leaves out ``train()``'s bookkeeping (metric log, flight
recorder, checkpoints, signal handlers).  PERF.md lists that difference.

The window: after two warm-up steps and a ``block_until_ready`` fence, steps
are dispatched until a fence shows ``--seconds`` have passed; the host never
runs more than ``RUN_AHEAD`` steps ahead of the device (it waits for the
loss of the step dispatched ``RUN_AHEAD`` steps ago, which never leaves the
device without a queued step; ``train()`` itself reads the metrics every
tenth step).  Tokens of all steps between the two fences over the wall time
between them, per chip.  ``block_until_ready`` is a true fence on this
attachment (measured in PR 22, PERF.md section 6).

Two seeds make a run.  ``--seed`` is the program's ``data_seed``: the order
of the record files, and so every batch.  The weights come from the cell's
own file (``weights_seed``, ``cell_weights_seed`` below) through
``Trainer.init_state(first_batch, seed=...)``, so that every run of a cell
trains the same model on other data: a rank that holds a share of the experts
draws the rows routed to it once, in the file, and not with every seed of a
check (``benchmark/README.md``, 'What --seed decides').  One ``seeds:`` line a
run carries the checksums that show it.
"""
from __future__ import annotations

import math
import os
import shutil
import time
import zlib

from ..lib import data as data_mod
from ..lib.result import NoAccelerator, Result, footprint
from ..trace.reduce import newest_xplane

RUN_AHEAD = 2
WARMUP_STEPS = 2
SPANS = ("data_next", "dispatch", "fence")


def cell_weights_seed(cell) -> int:
    """The seed of the cell's weights, from the cell's own file
    (``weights_seed``, with ``weights_seed_why``): ``--seed`` deals out the
    data only, so every run of a cell trains the same router and the same
    tables on another order of the record files (``benchmark/README.md``,
    'What --seed decides').  A file without the key is an error, not a
    default: a cell that drew its weights from ``--seed`` would draw its held
    experts' rows anew with every pair of the check's runs."""
    seed, why = cell.spec.get("weights_seed"), cell.spec.get("weights_seed_why")
    if isinstance(seed, bool) or not isinstance(seed, int) \
            or not isinstance(why, str) or not why.strip():
        raise KeyError(
            f"cell {cell.name}: benchmark/workloads/{cell.name}.json needs an "
            f"integer \"weights_seed\" and a \"weights_seed_why\" that says "
            f"how it was chosen (benchmark/README.md, 'Choosing a cell's "
            f"weights_seed'); found {seed!r} and {why!r}")
    return seed


def weights_seed_and_origin(cell, override=None) -> tuple:
    """``(seed, where it came from)``: ``--weights-seed`` where a sweep or a
    control gave one, the cell's file otherwise."""
    if override is not None:
        return override, "--weights-seed"
    return cell_weights_seed(cell), "the cell's file"


def seeds_line(seed: int, origin: str, variables, batch) -> tuple:
    """``(line, counters)``: the weights' seed, a CRC-32 of two named
    parameters — the token embedding (the first parameter the graph walk
    makes) and the first parameter of the first sparse layer where the model
    has one (the router's matrix under the one-matrix routers) — and one of
    the first batch's tokens.  Two runs of a cell at different ``--seed``
    show the first two equal and the third different."""
    import numpy as np
    names = list(variables)
    named = names[:1] + [n for n in names if "/moe_" in n][:1]
    params = {n: zlib.crc32(np.ascontiguousarray(variables[n]))
              for n in named}
    tokens = zlib.crc32(np.ascontiguousarray(batch["token_x"]))
    line = (f"seeds: weights_seed {seed} ({origin}); "
            + "; ".join(f"{n} crc32 {v:08x}" for n, v in params.items())
            + f"; the first batch's tokens crc32 {tokens:08x}")
    return line, {"weights_seed": seed, "param_crc32": params,
                  "first_batch_crc32": tokens}


class _Compiles:
    """Counts what jax compiles or loads from its cache while ``on``."""

    def __init__(self):
        self.in_window = 0
        self.misses = 0
        self.on = False

    def duration(self, name, _secs, **_kw):
        if self.on and name == "/jax/core/compile/backend_compile_duration":
            self.in_window += 1

    def event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def run(ctx) -> Result:
    import jax

    cell, log = ctx.cell, ctx.log
    devices = jax.devices()
    platform = devices[0].platform
    if (platform != "tpu" and not ctx.rehearsal) or len(devices) != cell.chips:
        raise NoAccelerator(
            f"cell {cell.name} needs {cell.chips} TPU chip(s); jax found "
            f"{len(devices)} x {platform!r}")
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles.duration)
    jax.monitoring.register_event_listener(compiles.event)

    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.core import sharding as shardlib
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.run.train_loop import _AsyncFeeder, make_dataset
    from homebrewnlp_tpu.train import Trainer
    from homebrewnlp_tpu.utils.compile_cache import install_compile_cache
    log(f"compile cache: {install_compile_cache()}")
    t_import = time.monotonic()

    traffic = cell.traffic(ctx.rehearsal)
    config = cell.model_config(ctx.rehearsal)
    weights_seed, origin = weights_seed_and_origin(cell, ctx.weights_seed)
    run_dir = os.path.join(ctx.out_dir, "run")
    shutil.rmtree(run_dir, ignore_errors=True)
    config.update(
        data_seed=int(ctx.seed), model_path=run_dir,
        dataset_configs=[{"path": data_mod.ensure_records(
            int(traffic["corpus_bytes"]), int(traffic["file_tokens"]),
            ctx.rehearsal), "type": "text", "weight": 1}])
    params = ModelParameter(config)
    t_data = time.monotonic()

    mesh = shardlib.build_mesh(params) if len(devices) > 1 else None
    model = Model(params)
    trainer = Trainer(params, model, mesh=mesh)
    data = make_dataset(params, mesh=mesh)
    try:
        first_batch = next(iter(data))
        state = trainer.init_state(first_batch, seed=weights_seed)
        jax.block_until_ready(state.variables)
        log(shardlib.placement_report(state.variables, mesh))
        line, seeds = seeds_line(weights_seed, origin, state.variables,
                                 first_batch)
        log(line)
        t_init = time.monotonic()

        checks = _reference_check(ctx, config, model, trainer, mesh, state,
                                  first_batch)
        t_check = time.monotonic()

        data_it = iter(data)
        if params.async_input_transfer:
            data_it = _AsyncFeeder(data_it, trainer.place_batch)
        batch, losses = first_batch, []
        for _ in range(WARMUP_STEPS):
            state, metrics = trainer.step(state, batch)
            losses.append(metrics["loss"])
            batch = next(data_it)
        jax.block_until_ready(losses[-1])
        t_warm = time.monotonic()
        first_loss = float(losses[0])

        # ---- the measured window: profiler off, pipeline running
        tokens_per_step = params.train_batch_size * params.sequence_length \
            * params.macro_batching
        losses = []
        compiles.on = True
        t0 = time.monotonic()
        while True:
            state, metrics = trainer.step(state, batch)
            losses.append(metrics["loss"])
            batch = next(data_it)
            if len(losses) > RUN_AHEAD:
                jax.block_until_ready(losses[-1 - RUN_AHEAD])
                if time.monotonic() - t0 >= ctx.seconds:
                    break
        jax.block_until_ready(losses[-1])
        t1 = time.monotonic()
        compiles.on = False
        window = [float(v) for v in losses]
        rate = len(window) * tokens_per_step / (t1 - t0) / len(devices)

        trace_dir = None
        if ctx.trace:
            trace_dir = _traced_window(ctx, trainer, state, batch, data_it,
                                       int(traffic["trace_steps"]))
    finally:
        data.close()

    # the loss has to fall on the learnable corpus: the mean of the
    # window's last quarter lies below the first step's loss by more than
    # one step of the bfloat16 it is reported in
    tail = window[-max(1, len(window) // 4):]
    tail_mean = sum(tail) / len(tail)
    nonfinite = sum(not math.isfinite(v) for v in window)
    checks.update(
        first_step_loss=first_loss,
        loss_agrees=abs(first_loss - checks["reference_loss"])
        <= checks["loss_tolerance"],
        losses_finite=nonfinite == 0,
        loss_drop=first_loss - tail_mean,
        loss_fell=first_loss - tail_mean > checks["loss_tolerance"],
        compiles_in_window=compiles.in_window,
        no_compile_in_window=compiles.in_window == 0)
    correct = all(checks[k] for k in ("loss_agrees", "logits_agree",
                                      "losses_finite", "loss_fell",
                                      "no_compile_in_window"))
    # each number compared beside its limit, for the run's last lines
    compared = {
        "logit_error": {"value": checks["logit_error"],
                        "at_most": checks["logit_tolerance"]},
        "loss_gap": {"value": abs(first_loss - checks["reference_loss"]),
                     "at_most": checks["loss_tolerance"]},
        "loss_drop": {"value": checks["loss_drop"],
                      "above": checks["loss_tolerance"]},
        "nonfinite_losses": {"value": nonfinite, "at_most": 0},
        "compiles_in_window": {"value": compiles.in_window, "at_most": 0}}
    log(f"window: {len(window)} steps in {t1 - t0:.4f}s; first step's loss "
        f"{first_loss:.4f}, the window's: "
        + " ".join(f"{v:.4f}" for v in window[:12])
        + (f" .. {window[-1]:.4f}" if len(window) > 12 else ""))
    stats = [d.memory_stats() or {} for d in devices]
    log(f"memory_stats of the first chip: {stats[0]}")
    peak, limit = footprint(stats)
    return Result(
        end_to_end={"train_tokens_per_sec_chip": rate,
                    "setup_s": t0 - ctx.t_start},
        correct=correct, checks=checks, compared=compared,
        attempted=len(window), failed=nonfinite,
        device={"platform": platform, "kind": devices[0].device_kind,
                "count": len(devices), "memory_peak_bytes": int(peak)},
        spans={"import_s": t_import - ctx.t_start, "data_s": t_data - t_import,
               "init_s": t_init - t_data, "check_s": t_check - t_init,
               "compile_s": t_warm - t_check, "window_s": t1 - t0},
        counters={"steps": len(window), "tokens_per_step": tokens_per_step,
                  "cache_misses": compiles.misses,
                  "memory_limit_bytes": int(limit), **seeds},
        trace_path=newest_xplane(trace_dir), trace_window="bench_window",
        trace_spans=SPANS)


def _reference_check(ctx, config, model, trainer, mesh, state, batch) -> dict:
    """The program's forward pass against the plain reference on the first
    batch, with the seeded weights, before any step has changed them."""
    import jax
    import numpy as np

    from ..lib.cell import load_reference
    from ..reference import common
    ref = load_reference(ctx.cell.config_name)
    tokens = np.asarray(batch["token_x"])[..., 0]
    targets = np.asarray(batch["token_y"])[..., 0]
    # a few rows at a time, so that the reference's float32 activations
    # fit beside the train state whatever the batch
    rows = int(ctx.cell.spec["correct"]["reference_rows"])
    want = np.concatenate([
        np.asarray(ref.forward(state.variables, tokens[i:i + rows], config))
        for i in range(0, len(tokens), rows)])
    forward = jax.jit(lambda v, b: model.apply(v, b, mesh=mesh).token_out.data)
    got = np.asarray(forward(state.variables, trainer.place_batch(batch))
                     .astype(np.float32))[:, :, 0, :]
    err = float(np.max(np.abs(want - got)) / np.max(np.abs(want)))
    tolerance = float(ctx.cell.spec["correct"]["logit_tolerance"])
    ctx.log(f"reference: max|logit - reference| / max|reference| = "
            f"{err:.6f} (tolerance {tolerance}), max|reference| = "
            f"{float(np.max(np.abs(want))):.4f}")
    return {"logit_error": err, "logit_tolerance": tolerance,
            "logits_agree": err <= tolerance,
            "reference_loss": float(common.loss_of(want, targets,
                                                   config["z_loss"])),
            "loss_tolerance": float(ctx.cell.spec["correct"]
                                    ["loss_tolerance"])}


def _traced_window(ctx, trainer, state, batch, data_it, steps: int) -> str:
    """A short window of its own under the profiler, with the harness's
    spans on the trace's clock; returns the trace's directory."""
    import jax
    trace_dir = os.path.join(ctx.out_dir, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    span = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with span("bench_window"):
            losses = []
            for _ in range(steps):
                with span("dispatch"):
                    state, metrics = trainer.step(state, batch)
                losses.append(metrics["loss"])
                with span("data_next"):
                    batch = next(data_it)
                if len(losses) > RUN_AHEAD:
                    with span("fence"):
                        jax.block_until_ready(losses[-1 - RUN_AHEAD])
            with span("fence"):
                jax.block_until_ready(losses[-1])
    finally:
        jax.profiler.stop_trace()
    return trace_dir
