#!/usr/bin/env python3
"""Measured multi-process scaling: tokens/sec/chip at 1→2→4→8 processes.

The dryrun census (scripts/pod_lowering.py, __graft_entry__.dryrun_multichip)
proves every parallel strategy COMPILES and partitions; this actually RUNS
them across real processes and measures the scaling curve — the Mesh-TF
claim (PAPERS.md 1811.02084: one model definition transparently scaled) and
the pjit-TPUv4 measurement template (PAPERS.md 2204.06514) reproduced on the
CPU multiprocess rig.

For each strategy × process count the parent fans out N coordinator-wired
worker processes (JAX_PLATFORMS=cpu, 2 virtual devices each, gloo
collectives via homebrewnlp_tpu.distributed.bootstrap — the same launch path
as scripts/run_manager.py --num-processes).  Every worker runs the REAL
jitted+donated train step over the strategy's mesh; the chief reports
measured tokens/sec, the parent derives per-chip throughput and scaling
efficiency vs the 1-process baseline (weak scaling: the global batch grows
with the data axis, per-chip work constant).

Pipeline-parallel schedules stay a loudly-SKIPPED row: jax 0.4.37's
partial-manual PartitionId gap (analysis/mesh_audit.py classify_env_gap)
breaks their compile regardless of process count; the row records the
reason so a capable environment turns it back into a measurement.

Usage:
  python scripts/bench_multihost.py                     # full sweep
  python scripts/bench_multihost.py --procs 1,2 --strategies dp_tp
  python scripts/bench_multihost.py --out MULTICHIP_MEASURED.json

Writes one JSON report (default MULTICHIP_MEASURED.json at the repo root)
next to the dryrun MULTICHIP rows; nonzero exit when any non-skipped
strategy produced no measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import subprocess
import sys
import time
import typing

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, ".."))

#: virtual CPU devices per process: 2, so the model/sequence axis exists at
#: EVERY process count (tp/sp inside the process, dp across processes — the
#: realistic pod layout) and the 1-process baseline runs the same program
DEVICES_PER_PROCESS = 2

#: timed steps per measurement (after one executed warmup step)
DEFAULT_STEPS = 8

_SEQ = 64

# axis names inside mesh_shape_override / layout_override dicts are
# config-schema keys (the same spelling every shipped config JSON uses),
# not PartitionSpec literals — outside the mesh-axis-literal rule's scope
STRATEGIES: typing.Dict[str, dict] = {
    # batch over 'data' (cross-process), heads over 'model' (in-process)
    "dp_tp": dict(heads=8),
    # ring-attention sequence parallelism: dot-product attention over a
    # data x sequence mesh
    "ring_sp": dict(
        block_config=[{"layer": ["norm-shift-scale-features-group",
                                 "attention-dot_product-context"]}],
        memory_reduction_strategy="none"),
    # routed top-k MoE, experts sharded over 'model' (dispatch/combine
    # all-to-alls cross the expert axis)
    "moe_ep": dict(
        experts=4, heads=2, features_per_head=32, moe_top_k=2,
        moe_capacity_factor=2.0,
        block_config=[{"layer": [
            "norm-shift-scale-features-group",
            "feed_forward-in:relu-in:mixture_of_experts-in:routed"]}],
        memory_reduction_strategy="none",
        layout_override={"experts": "model", "heads": None}),
    # pipeline parallelism: attempted, expected to classify as an env gap
    # on jax 0.4.37 (partial-manual PartitionId)
    "pp_gpipe": dict(depth=2, heads=8),
}


def _mesh_override(strategy: str, nproc: int) -> dict:
    inner = {"dp_tp": "model", "moe_ep": "model", "ring_sp": "sequence",
             "pp_gpipe": "pipe"}[strategy]
    return {"data": nproc, inner: DEVICES_PER_PROCESS}


def _free_port() -> int:
    from homebrewnlp_tpu.distributed.bootstrap import free_port
    return free_port()


# ---- worker ----------------------------------------------------------------

def collectives_worker(steps: int, sizes_mb: typing.List[float]) -> int:
    """Collectives-only microbenchmark: timed cross-process all-reduces of
    gradient-sized buffers with NO model step, so the scaling curve
    separates gloo/TCP collective cost from core oversubscription (the
    caveat previously folded into one efficiency number).  Each process
    contributes a distinct full-size buffer — a replicated psum would let
    XLA lower a local multiply instead of real communication."""
    from homebrewnlp_tpu.distributed import bootstrap
    bootstrap.maybe_initialize(verbose=False)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from homebrewnlp_tpu.core import sharding as shardlib

    nproc = jax.process_count()
    pid = jax.process_index()
    devices = jax.devices()
    mesh = Mesh(np.asarray(devices).reshape(-1), (shardlib.DATA_AXIS,))
    nshard = len(devices)
    rows = []
    for size_mb in sizes_mb:
        n = max(1, int(size_mb * (1 << 20) // 4))

        def body(x):
            return jax.lax.psum(x[0], shardlib.DATA_AXIS)

        fn = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P(shardlib.DATA_AXIS), out_specs=P(),
            axis_names={shardlib.DATA_AXIS}, check_vma=False))
        x = jax.device_put(
            np.arange(nshard * n, dtype=np.float32).reshape(nshard, n)
            / (nshard * n), NamedSharding(mesh, P(shardlib.DATA_AXIS)))
        jax.block_until_ready(fn(x))  # compile + warm
        t0 = time.monotonic()
        for _ in range(steps):
            out = fn(x)
        jax.block_until_ready(out)
        wall = time.monotonic() - t0
        ms = wall / steps * 1e3
        rows.append({"size_mb": size_mb, "ms_per_allreduce": round(ms, 3),
                     # bus bytes ~ 2x buffer per ring all-reduce; report
                     # the simple buffer-bytes/time rate for comparability
                     "gb_per_sec": round(n * 4 / (ms / 1e3) / 1e9, 3)})
    if pid == 0:
        print("BENCH_MULTIHOST_RESULT " + json.dumps({
            "kind": "collectives", "processes": nproc,
            "devices": len(devices), "steps": steps, "rows": rows}),
            flush=True)
    return 0


def worker(strategy: str, steps: int, batch_per_slice: int,
           grad_allreduce: str = "") -> int:
    from homebrewnlp_tpu.distributed import bootstrap
    multi = bootstrap.maybe_initialize(verbose=False)
    import jax
    import numpy as np

    import __graft_entry__ as graft
    from homebrewnlp_tpu.analysis import mesh_audit
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.core import sharding as shardlib
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.train import Trainer

    nproc = jax.process_count()
    pid = jax.process_index()
    assert multi or nproc == 1
    devices = jax.devices()
    ndev = len(devices)
    overrides = dict(STRATEGIES[strategy])
    if grad_allreduce:
        # the grad-allreduce A/B: both legs run remat_policy=save_dots (the
        # one policy the bucketed partial-manual region supports on this
        # jax), so the ONLY variable between fused and bucketed rows is the
        # collective schedule
        overrides.update(grad_allreduce=grad_allreduce,
                         remat_policy="save_dots")
    global_batch = batch_per_slice * nproc
    params = ModelParameter(graft._config(
        sequence_length=_SEQ, train_batch_size=global_batch,
        tpu_size=ndev, mesh_shape_override=_mesh_override(strategy, nproc),
        **overrides))
    mesh = shardlib.build_mesh(params)
    trainer = Trainer(params, Model(params), mesh=mesh)

    slice_index, slice_count = shardlib.process_data_slice(mesh) \
        if nproc > 1 else (0, 1)
    local = global_batch // slice_count
    rng = np.random.default_rng(1234 + slice_index)
    x = rng.integers(0, params.vocab_size, (local, _SEQ, 1))
    batch = {"token_x": np.asarray(x, np.int32),
             "token_y": np.asarray((x + 1) % params.vocab_size, np.int32)}

    try:
        state = trainer.init_state(batch)
        # warmup: compiles the REAL donated jitted step (the exact program
        # train_loop runs), executes once
        state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics["loss"])
    except Exception as exc:  # noqa: BLE001 — classified below
        reason = mesh_audit.classify_env_gap(exc)
        if reason is None:
            raise
        if pid == 0:
            print("BENCH_MULTIHOST_RESULT "
                  + json.dumps({"strategy": strategy, "processes": nproc,
                                "skipped": reason}), flush=True)
        return 0

    t0 = time.monotonic()
    for _ in range(steps):
        state, metrics = trainer.step(state, batch)
    loss = float(np.asarray(jax.device_get(metrics["loss"])))
    wall = time.monotonic() - t0
    tokens = steps * global_batch * _SEQ
    if pid == 0:
        row = {
            "strategy": strategy, "processes": nproc, "devices": ndev,
            "mesh": dict((str(k), int(v)) for k, v in mesh.shape.items()),
            "global_batch": global_batch, "sequence_length": _SEQ,
            "steps": steps, "wall_s": round(wall, 4),
            "loss": round(loss, 4),
            "tokens_per_sec": round(tokens / wall, 1),
            "tokens_per_sec_per_chip": round(tokens / wall / ndev, 1),
        }
        if grad_allreduce:
            row["grad_allreduce"] = grad_allreduce
        print("BENCH_MULTIHOST_RESULT " + json.dumps(row), flush=True)
    return 0


# ---- parent ----------------------------------------------------------------

def _spawn_fleet(strategy: str, nproc: int, steps: int, batch_per_slice: int,
                 timeout: int, retries: int = 1,
                 extra_args: typing.Sequence[str] = ()
                 ) -> typing.Optional[dict]:
    """One fleet, retried once on a nonzero exit: wide fan-outs on a host
    with fewer cores than processes occasionally starve the coordination
    heartbeat (the whole fleet SIGABRTs with 'another task died'), which
    is scheduler pressure, not a property of the strategy under test."""
    from tests.multihost_test import starvation_retry_reason
    for attempt in range(retries + 1):
        row, rcs, outs = _spawn_fleet_once(strategy, nproc, steps,
                                           batch_per_slice, timeout,
                                           extra_args)
        if row is not None:
            return row
        if attempt < retries:
            reason = starvation_retry_reason(rcs, outs)
            print(f"  {strategy} x{nproc}: retrying after fleet failure"
                  + (f" — {reason}" if reason else ""), flush=True)
    return None


def _spawn_fleet_once(strategy: str, nproc: int, steps: int,
                      batch_per_slice: int, timeout: int,
                      extra_args: typing.Sequence[str] = ()
                      ) -> typing.Tuple[typing.Optional[dict],
                                        typing.List[int],
                                        typing.List[str]]:
    """One attempt; returns ``(result_row_or_None, worker_rcs, worker
    outputs)`` so the retry loop can classify the failure shape (the
    shared 1-core gloo-SIGABRT starvation classifier in
    tests/multihost_test.py)."""
    port = _free_port()
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    procs = []
    for pid in range(nproc):
        env = dict(os.environ,
                   HBNLP_COORDINATOR=f"localhost:{port}",
                   HBNLP_NUM_PROCESSES=str(nproc),
                   HBNLP_PROCESS_ID=str(pid),
                   JAX_PLATFORMS="cpu",
                   XLA_FLAGS=flags + " --xla_force_host_platform_device_"
                   f"count={DEVICES_PER_PROCESS}")
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker",
             "--strategies", strategy, "--steps", str(steps),
             "--batch-per-slice", str(batch_per_slice)] + list(extra_args),
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            print(f"  {strategy} x{nproc}: TIMEOUT after {timeout}s",
                  flush=True)
            return None, [p.returncode or -9 for p in procs], outs
        outs.append(out)
    rcs = [p.returncode for p in procs]
    for pid, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            print(f"  {strategy} x{nproc}: worker {pid} failed "
                  f"(rc={p.returncode}):\n{out[-2000:]}", flush=True)
            return None, rcs, outs
    for out in outs:
        for line in out.splitlines():
            if line.startswith("BENCH_MULTIHOST_RESULT "):
                return json.loads(line.split(" ", 1)[1]), rcs, outs
    print(f"  {strategy} x{nproc}: no result line emitted", flush=True)
    return None, rcs, outs


def run_sweep(strategies: typing.List[str], proc_counts: typing.List[int],
              steps: int, batch_per_slice: int, timeout: int) -> dict:
    report: dict = {
        "backend": "cpu", "devices_per_process": DEVICES_PER_PROCESS,
        "sequence_length": _SEQ, "steps_per_point": steps,
        "note": ("measured multi-process scaling on the CPU rig (gloo "
                 "collectives); weak scaling — global batch grows with the "
                 "data axis, per-chip work constant.  CPU numbers anchor "
                 "the SHAPE of the curve, not TPU magnitudes; on a box "
                 "with fewer cores than processes the efficiency column "
                 "additionally folds in core oversubscription (record "
                 "host_cores alongside when comparing rounds)."),
        "host_cores": os.cpu_count(),
        "strategies": {},
    }
    for strategy in strategies:
        rows = []
        base_per_chip = None
        for nproc in proc_counts:
            t0 = time.monotonic()
            row = _spawn_fleet(strategy, nproc, steps, batch_per_slice,
                               timeout)
            if row is None:
                rows.append({"processes": nproc, "error": "no result"})
                continue
            if "skipped" in row:
                print(f"  {strategy} x{nproc}: SKIPPED — {row['skipped']}",
                      flush=True)
                rows.append(row)
                # the gap is jax-version-, not process-count-, dependent:
                # one classified skip covers the strategy
                break
            if nproc == min(proc_counts) and row.get("tokens_per_sec_per_chip"):
                base_per_chip = row["tokens_per_sec_per_chip"]
            if base_per_chip:
                row["scaling_efficiency_vs_1proc"] = round(
                    row["tokens_per_sec_per_chip"] / base_per_chip, 3)
            print(f"  {strategy} x{nproc}: "
                  f"{row['tokens_per_sec_per_chip']} tok/s/chip "
                  f"(eff {row.get('scaling_efficiency_vs_1proc', 1.0)}) "
                  f"[{time.monotonic() - t0:.0f}s incl. compile]",
                  flush=True)
            rows.append(row)
        report["strategies"][strategy] = rows
    return report


def run_collectives_sweep(proc_counts: typing.List[int], steps: int,
                          sizes_mb: typing.List[float], timeout: int,
                          batch_per_slice: int) -> typing.List[dict]:
    """The collectives-only rows: all-reduce of gradient-sized buffers at
    each process count, no model step (docs/DISTRIBUTED.md 'Measured
    scaling')."""
    rows = []
    for nproc in proc_counts:
        t0 = time.monotonic()
        row = _spawn_fleet(
            "dp_tp", nproc, steps, batch_per_slice, timeout,
            extra_args=["--collectives",
                        "--sizes-mb", ",".join(str(s) for s in sizes_mb)])
        if row is None:
            rows.append({"processes": nproc, "error": "no result"})
            continue
        summary = " ".join(
            f"{r['size_mb']}MB={r['ms_per_allreduce']}ms" for r in row["rows"])
        print(f"  collectives x{nproc}: {summary} "
              f"[{time.monotonic() - t0:.0f}s]", flush=True)
        rows.append(row)
    return rows


def run_grad_ab_sweep(proc_counts: typing.List[int], steps: int,
                      batch_per_slice: int, timeout: int
                      ) -> typing.List[dict]:
    """The fused-vs-bucketed gradient-allreduce A/B on the dp_tp strategy
    (the one the bucketed policy targets), both legs at
    remat_policy=save_dots so the collective schedule is the only
    variable."""
    rows = []
    for nproc in proc_counts:
        pair: typing.Dict[str, typing.Any] = {"processes": nproc}
        for variant in ("fused", "bucketed"):
            t0 = time.monotonic()
            row = _spawn_fleet("dp_tp", nproc, steps, batch_per_slice,
                               timeout,
                               extra_args=["--grad-allreduce", variant])
            if row is None:
                pair[variant] = {"error": "no result"}
                continue
            pair[variant] = {k: row[k] for k in
                             ("tokens_per_sec", "tokens_per_sec_per_chip",
                              "wall_s", "loss") if k in row}
            print(f"  grad_ab {variant} x{nproc}: "
                  f"{row.get('tokens_per_sec_per_chip')} tok/s/chip "
                  f"[{time.monotonic() - t0:.0f}s incl. compile]",
                  flush=True)
        f = pair.get("fused", {}).get("tokens_per_sec_per_chip")
        b = pair.get("bucketed", {}).get("tokens_per_sec_per_chip")
        if f and b:
            pair["bucketed_vs_fused"] = round(b / f, 3)
        rows.append(pair)
    return rows


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--collectives", action="store_true",
                    help="(worker/sweep) collectives-only microbenchmark: "
                         "timed all-reduces of gradient-sized buffers, no "
                         "model step")
    ap.add_argument("--sizes-mb", default="1,4,16", dest="sizes_mb",
                    help="buffer sizes (MiB) for the collectives rows")
    ap.add_argument("--grad-allreduce", default="", dest="grad_allreduce",
                    choices=["", "fused", "bucketed"],
                    help="(worker) run the dp_tp step under this "
                         "grad_allreduce policy at remat_policy=save_dots")
    ap.add_argument("--grad-ab", action="store_true", dest="grad_ab",
                    help="run the fused-vs-bucketed grad-allreduce A/B "
                         "sweep on dp_tp (adds the grad_allreduce_ab rows)")
    ap.add_argument("--strategies", default="dp_tp,ring_sp,moe_ep,pp_gpipe")
    ap.add_argument("--procs", default="1,2,4,8")
    ap.add_argument("--steps", type=int, default=DEFAULT_STEPS)
    ap.add_argument("--batch-per-slice", type=int, default=8,
                    dest="batch_per_slice")
    ap.add_argument("--timeout", type=int, default=600,
                    help="seconds per (strategy, nproc) fleet")
    ap.add_argument("--skip-strategy-sweep", action="store_true",
                    dest="skip_strategy_sweep",
                    help="only run the requested extra sweeps "
                         "(--grad-ab / --collectives), merging into --out")
    ap.add_argument("--out", default=os.path.join(HERE, "..",
                                                  "MULTICHIP_MEASURED.json"))
    args = ap.parse_args()
    sizes_mb = [float(s) for s in args.sizes_mb.split(",") if s]
    strategies = [s for s in args.strategies.split(",") if s]
    unknown = [s for s in strategies if s not in STRATEGIES]
    if unknown:
        ap.error(f"unknown strategies {unknown}; have {list(STRATEGIES)}")
    if args.worker:
        if args.collectives:
            return collectives_worker(args.steps, sizes_mb)
        return worker(strategies[0], args.steps, args.batch_per_slice,
                      grad_allreduce=args.grad_allreduce)
    proc_counts = sorted(int(p) for p in args.procs.split(","))
    out = os.path.abspath(args.out)
    if args.skip_strategy_sweep:
        # merge the extra sweeps into the existing report; a missing --out
        # starts one from scratch rather than running the multi-hour
        # strategy sweep the flag explicitly asked to skip
        report = {}
        if os.path.exists(out):
            with open(out) as f:
                report = json.load(f)
    else:
        report = run_sweep(strategies, proc_counts, args.steps,
                           args.batch_per_slice, args.timeout)
    if args.collectives or not args.skip_strategy_sweep:
        report["collectives"] = run_collectives_sweep(
            proc_counts, max(args.steps, 8), sizes_mb, args.timeout,
            args.batch_per_slice)
    if args.grad_ab:
        report["grad_allreduce_ab"] = run_grad_ab_sweep(
            [p for p in proc_counts if p > 1] or proc_counts,
            args.steps, args.batch_per_slice, args.timeout)
    with open(out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"wrote {out}")
    # the sweep above forced the CPU rig (gloo/TCP, virtual devices): keep
    # the silicon queue loud, like bench_decode.py --tpu-recheck does
    print("NOTE: CPU-rig measurement — gloo/TCP collectives on an "
          "oversubscribed host anchor the curve SHAPE, not TPU "
          "magnitudes.  Queued on silicon (BASELINE.md 'Queued on "
          "silicon'): the per-strategy 1-to-8-chip curve, the "
          "fused-vs-bucketed grad-allreduce A/B (--grad-ab), and the "
          "collectives-only rows (--collectives) on ICI.", flush=True)
    measured = [s for s, rows in report["strategies"].items()
                if any("tokens_per_sec_per_chip" in r for r in rows)]
    skipped = [s for s, rows in report["strategies"].items()
               if any("skipped" in r for r in rows)]
    failed = [s for s, rows in report["strategies"].items()
              if s not in measured and s not in skipped]
    print(f"measured: {measured}; skipped (env gap): {skipped}; "
          f"failed: {failed}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
