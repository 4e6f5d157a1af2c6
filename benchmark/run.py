#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Resolves the cell, its configuration, its driver and its per-layer metrics
by name (``BENCHMARK.json``, ``benchmark/workloads/``, ``configs/``,
``drivers/``, ``metrics/``), runs the driver, and prints the result as the
last line of its output: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, in a traced run, ``breakdown``.  Everything
else worth reading goes on earlier lines and into ``benchmark/out/<cell>/``.

It measures the chip: with no TPU, with another number of chips than the
cell asks for, or outside a checkout of the program it exits non-zero and
prints no result.  ``--rehearse-cpu`` runs the same path at the cell's toy
size on the CPU to exercise the harness; a rehearsal prints no result line
and exits 10.
"""
import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EXIT_NO_ACCELERATOR = 3
EXIT_NOT_A_CHECKOUT = 4
EXIT_REHEARSAL = 10


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--weights-seed", type=int, default=None,
                    help="a sweep's override of the cell file's "
                         "weights_seed (sweep_weights_seed.py); the "
                         "driver's check never gives it")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="toy size on the CPU; exercises the harness, "
                         "prints no result line, exits 10")
    args = ap.parse_args(argv)

    from benchmark.lib import cell as cell_mod
    from benchmark.lib.result import Context, NoAccelerator, Run
    try:
        cell = cell_mod.load_cell(args.workload)
    except cell_mod.NotACheckout as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return EXIT_NOT_A_CHECKOUT
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={cell.chips}").strip()

    out_dir = cell_mod.out_dir(cell.name, args.rehearse_cpu)
    log_file = open(os.path.join(out_dir, "run.log"), "w")

    def log(line: str) -> None:
        print(line, flush=True)
        log_file.write(line + "\n")
        log_file.flush()

    log(f"cell {cell.name}: config {cell.config_name}, driver "
        f"{cell.spec['driver']}, chips {cell.chips}, seed {args.seed}, "
        f"seconds {args.seconds}, trace {args.trace}"
        + (" — REHEARSAL on the CPU at a toy size" if args.rehearse_cpu
           else ""))
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), rehearsal=args.rehearse_cpu,
                  t_start=T_START, out_dir=out_dir, log=log,
                  weights_seed=args.weights_seed)
    try:
        result = cell_mod.load_driver(cell.spec["driver"]).run(ctx)
    except NoAccelerator as exc:
        print(f"run.py: {exc}", file=sys.stderr)
        return EXIT_NO_ACCELERATOR

    log("spans: " + json.dumps(result.spans))
    log("counters: " + json.dumps({k: v for k, v in result.counters.items()
                                   if k != "histograms"}))
    log("checks: " + json.dumps(result.checks))
    device = dict(result.device)
    line = {"correct": bool(result.correct), "attempted": result.attempted,
            "failed": result.failed, "metrics": {}, "device": device}
    if args.trace:
        reduced = None
        if result.trace_path:
            from benchmark.trace import reduce as reduce_mod
            try:
                reduced = reduce_mod.reduce(
                    reduce_mod.load(result.trace_path), result.trace_window,
                    result.trace_spans)
            except ValueError as exc:     # a trace with no device plane
                log(f"trace: {exc}")
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = reduce_mod.breakdown(reduced)
            with open(os.path.join(out_dir, "trace_reduced.json"), "w") as f:
                json.dump({k: v for k, v in reduced.items()
                           if k != "per_device"}, f, indent=1)
        run = Run(cell=cell, config=cell.model_config(args.rehearse_cpu),
                  result=result, trace=reduced)
        for metric in cell.per_layer:
            try:
                value = cell_mod.load_metric(metric["name"]).read(run)
            except LookupError as exc:
                # no peaks for this device, no cost function for a kernel:
                # an error on the chip; a rehearsal on the CPU goes on
                if not args.rehearse_cpu:
                    raise
                log(f"per-layer {metric['name']}: {exc}")
                continue
            if value is None:
                log(f"per-layer {metric['name']}: nothing to read")
                continue
            line["metrics"][metric["name"]] = {"value": value,
                                               "unit": metric["unit"]}
        for note in run.notes:
            log(f"note: {note}")
    else:
        for metric in cell.end_to_end:
            line["metrics"][metric["name"]] = {
                "value": result.end_to_end[metric["name"]],
                "unit": metric["unit"]}
    # what decided ``correct``, each number beside its limit: the line's
    # last key and the last lines on standard error
    line["compared"] = result.compared
    with open(os.path.join(out_dir, "result.json"), "w") as f:
        json.dump({"line": line, "spans": result.spans,
                   "counters": result.counters, "checks": result.checks,
                   "end_to_end": result.end_to_end, "seed": args.seed,
                   "seconds": args.seconds, "trace": args.trace}, f, indent=1)
    log_file.close()
    if args.rehearse_cpu:
        # names only: a number from a CPU run never stands under a device
        # metric's name (the numbers are in out/rehearsal/<cell>/result.json
        # for the harness's own tests)
        print("REHEARSAL (not a result): correct=%s attempted=%d failed=%d "
              "metrics=%s" % (line["correct"], line["attempted"],
                              line["failed"], sorted(line["metrics"])),
              flush=True)
        return EXIT_REHEARSAL
    print(json.dumps(line), flush=True)
    for name, entry in result.compared.items():
        print(f"compared {name}: " + " ".join(
            f"{k} {v}" for k, v in entry.items()), file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
