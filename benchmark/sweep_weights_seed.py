#!/usr/bin/env python3
"""Choose a held-expert cell's ``weights_seed``.  Run once, when the cell is
defined; the cell's file then holds the seed as a number, with the sweep's
readings in ``weights_seed_why``.

    chiprun --timeout 1800 -- python benchmark/sweep_weights_seed.py \\
        --workload train_laguna_s_2_1_ep32_s8k

A rank that holds a share of its experts does work that follows its weights:
at seeded initialisation a token's type all but decides its experts, so how
many (token, choice) pairs land on the held experts is a draw of the weights'
seed (``benchmark/README.md``, 'What --seed decides').  The cell keeps ONE
draw, the one in the middle of what the seeds give: this runs the cell at
``--seconds 10 --trace 1`` (``moe_held_pair_share`` and ``step_stall_share``
are read beside a trace; the rate is the untraced window's either way) with
``--weights-seed`` 0..7 and one ``--seed``, each run a process of its own
(this parent stays off jax), a stalled run (``step_stall_share`` over
``STALLED_PERCENT``) once more, and takes the seed whose
``train_tokens_per_sec_chip`` is the LOWER MEDIAN of the eight (the fourth
from below).  It prints one JSON row a run, then the choice with the text for
``weights_seed_why``.  It edits nothing: write the two keys into the cell's
file by hand.
"""
import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
RATE = "train_tokens_per_sec_chip"
#: a run whose steps took this much of the window beyond their median stalled
#: (a 2.5 s freeze of the host is 25% of 10 s, a 0.5 s one 5%; unstalled runs
#: of 10 s read 0.05-0.2% in the Nemotron cell and 0.27-1.2% in the Laguna
#: cell, whose steps follow the batch's routing: my chip runs, PR 57)
STALLED_PERCENT = 2.0


def one_run(args, weights_seed: int) -> dict:
    cmd = [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
           "--workload", args.workload, "--seed", str(args.seed),
           "--seconds", str(args.seconds), "--trace", "1",
           "--weights-seed", str(weights_seed)]
    if args.rehearse_cpu:
        cmd.append("--rehearse-cpu")
    done = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    if done.returncode != (10 if args.rehearse_cpu else 0):
        sys.stderr.write(done.stdout[-4000:] + done.stderr[-4000:])
        raise SystemExit(f"sweep_weights_seed.py: run.py exited "
                         f"{done.returncode} at weights seed {weights_seed}")
    from benchmark.lib import cell as cell_mod       # stdlib only: no jax
    with open(os.path.join(cell_mod.out_dir(args.workload, args.rehearse_cpu),
                           "result.json")) as f:
        result = json.load(f)
    metrics = {k: v["value"] for k, v in result["line"]["metrics"].items()}
    return {"weights_seed": weights_seed, "seed": args.seed,
            RATE: result["end_to_end"][RATE],
            "moe_held_pair_share": metrics.get("moe_held_pair_share"),
            "step_stall_share": metrics.get("step_stall_share"),
            "steps": result["counters"]["steps"],
            "param_crc32": result["counters"]["param_crc32"],
            "correct": result["line"]["correct"]}


def lower_median(rows: list) -> dict:
    ranked = sorted(rows, key=lambda r: r[RATE])
    return ranked[(len(ranked) - 1) // 2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1,
                    help="the one --seed (data) of every run")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--weights-seeds", default="0,1,2,3,4,5,6,7")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    rows = []
    for weights_seed in (int(s) for s in args.weights_seeds.split(",")):
        row = one_run(args, weights_seed)
        if (row["step_stall_share"] or 0.0) > STALLED_PERCENT:
            print(json.dumps(dict(row, stalled_and_run_again=True)),
                  flush=True)
            row = one_run(args, weights_seed)
        print(json.dumps(row), flush=True)
        rows.append(row)
    chosen = lower_median(rows)
    if args.rehearse_cpu:
        # names only: a CPU run's rate chooses nothing
        print(f"REHEARSAL (not a choice): {len(rows)} runs, every one "
              f"correct={all(r['correct'] for r in rows)}", flush=True)
        return 10
    print(json.dumps({
        "workload": args.workload, "weights_seed": chosen["weights_seed"],
        "weights_seed_why":
            f"the lower median of {len(rows)} by benchmark/README.md's rule "
            f"(sweep_weights_seed.py, --seed {args.seed}, --seconds "
            f"{args.seconds:g}, --trace 1, one v5e): weights seed -> "
            f"{RATE} (moe_held_pair_share %): " + ", ".join(
                f"{r['weights_seed']} -> {r[RATE]:.2f} "
                f"({r['moe_held_pair_share']})"
                for r in sorted(rows, key=lambda r: r[RATE]))}), flush=True)
    return 0 if all(r["correct"] for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
