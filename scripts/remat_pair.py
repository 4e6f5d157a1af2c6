#!/usr/bin/env python3
"""Parent and change of a memory-for-recompute change, side by side on one
chip at one seed.

    python scripts/remat_pair.py --parent runs/parent --seed <n> \
        [--cells <cell> ..] [--seconds 30] [--pairs 1] [--out chiprun_out/remat_pair]

For every cell (default: the two that run layer ``mamba``) one TRACED run of
each tree, parent first — ``step_device_ms``, the pass shares and the replay
by scope (the run's notes), ``remat_stash_share``,
``hbm_step_footprint_share``, ``memory_peak_bytes``, the first step's loss,
``logit_error`` and the stalls the step clock saw — then ``--pairs`` times
the untraced four parent, change, change, parent for
``train_tokens_per_sec_chip``.  Every run is
``benchmark/run.py`` of its own tree in its own process: this parent never
touches jax (a chip belongs to one process).  ``--parent`` is a checkout of
the parent commit inside the repo (``git archive <commit> | tar -x -C
runs/parent``: ``runs/`` is git-ignored and travels with ``chiprun``).  The
logs go to ``--out``; exit 1 where a run failed or was not ``correct``.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = ("train_nemotron_3_super_tp2_ep64_s16k",
         "train_granite_4_0_h_micro_long")
RATE = "train_tokens_per_sec_chip"
TRACED = ("step_device_ms", "pass_forward_time_share",
          "pass_replay_time_share", "pass_backward_time_share",
          "remat_stash_share", "hbm_step_footprint_share",
          "memory_peak_bytes", "step_stall_share")
NOTES = ("replay by scope", "pass shares of busy time")
COMPARED = ("logit_error", "loss_gap")


def run(tree: str, cell: str, seed: int, seconds: float, trace: int,
        log_path: str) -> dict:
    """One ``benchmark/run.py`` in ``tree``; the result line's metrics, the
    notes of :data:`NOTES`, the ``compared`` values, the first loss, the
    window's steps and seconds and the stalls the step clock printed."""
    cmd = [sys.executable, "benchmark/run.py", "--workload", cell, "--seed",
           str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    with open(log_path, "w") as log:
        code = subprocess.run(cmd, cwd=tree, stdout=log,
                              stderr=subprocess.STDOUT).returncode
    out = {"exit": code, "correct": False, "notes": {}, "stalls": []}
    with open(log_path) as log:
        for line in log:
            if line.startswith('{"correct"'):
                result = json.loads(line)
                out["correct"] = result["correct"]
                out.update({name: entry["value"] for name, entry
                            in result["metrics"].items()})
                out["memory_peak_bytes"] = result["device"].get(
                    "memory_peak_bytes")
            elif line.startswith("compared "):
                name, _, value = line[len("compared "):].split()[:3]
                out[name.rstrip(":")] = float(value)
            elif line.startswith("window:") and "first step's loss" in line:
                out["first_step_loss"] = float(
                    line.split("first step's loss")[1].split(",")[0])
                out["window"] = line.split(";")[0][len("window: "):]
            elif line.startswith("step clock: step "):
                out["stalls"].append(line.split(";")[0][len("step clock: "):])
            for note in NOTES:
                if note in line:
                    out["notes"][note] = line.strip()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--cells", nargs="+", default=list(CELLS))
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--pairs", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "remat_pair"))
    args = ap.parse_args(argv)
    trees = {"parent": os.path.abspath(args.parent), "change": ROOT}
    os.makedirs(args.out, exist_ok=True)
    ok = True
    for cell in args.cells:
        print(f"== {cell}, seed {args.seed}", flush=True)
        for side, tree in trees.items():
            got = run(tree, cell, args.seed, args.seconds, 1, os.path.join(
                args.out, f"{cell}.{side}.traced.log"))
            ok &= got["exit"] == 0 and got["correct"]
            print(f"{side} traced: exit {got['exit']} correct "
                  f"{got['correct']}; " + "; ".join(
                      f"{name} {got.get(name, 'not reported')}"
                      for name in (*TRACED, "first_step_loss", *COMPARED,
                                   "window", "stalls")), flush=True)
            for note in got["notes"].values():
                print(f"{side} {note}", flush=True)
        rates = {side: [] for side in trees}
        for pair in range(args.pairs):
            for at, side in enumerate(("parent", "change", "change",
                                       "parent")):
                got = run(trees[side], cell, args.seed, args.seconds, 0,
                          os.path.join(args.out,
                                       f"{cell}.{side}.{pair}_{at}.log"))
                ok &= got["exit"] == 0 and got["correct"]
                rates[side].append(got.get(RATE, float("nan")))
                print(f"{side} untraced: exit {got['exit']} correct "
                      f"{got['correct']}; {RATE} {rates[side][-1]}; "
                      + "; ".join(f"{name} {got.get(name)}" for name in (
                          "first_step_loss", *COMPARED, "window", "stalls")),
                      flush=True)
        if args.pairs:
            parent, change = (statistics.median(rates[side])
                              for side in ("parent", "change"))
            print(f"{cell}: {RATE} parent {parent} change {change} "
                  f"({100 * (change / parent - 1):+.3f}%)", flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
