#!/usr/bin/env python3
"""Find the knee of a serving cell: the highest offered rate the server
sustains.  Run once, when the cell is defined; the cell's file then holds
0.8 x the knee as a number (``traffic.rate_rps``).

    chiprun --timeout 1500 -- python benchmark/sweep_knee.py \\
        --workload serve_32big_mixer_steady --seed 1 --seconds 15 \\
        --rates 10,20,30,40,50,60

One server life; for every rate the cell's own traffic mix is offered for
``ramp_s + --seconds`` seconds, then the loop waits for the replies still
owed before the next rate starts.  Prints one JSON row a rate: latency of
the requests due in the window (from the due time), new tokens per second
received in it, failures, how many requests were still unanswered at the
window's close (a backlog that grows with the rate is the far side of the
knee), the server's own queue-wait and slot-occupancy numbers, and the
chip's memory.  The knee is read from the rows by hand: the highest rate
at which tokens/s still equals the offered load and the backlog at close
stays near the slot count.  ``--set key=json`` overrides a configuration
key for the sweep (``--set serve_slots=16``).
"""
import argparse
import json
import os
import sys
import time

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    from benchmark.drivers import serve
    from benchmark.lib import cell as cell_mod, stats, traffic as traffic_mod
    from benchmark.lib.result import Context
    cell = cell_mod.load_cell(args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    out_dir = cell_mod.out_dir(cell.name + ".sweep", args.rehearse_cpu)
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  trace=False, rehearsal=args.rehearse_cpu, t_start=T_START,
                  out_dir=out_dir, log=lambda s: print(s, flush=True))
    traffic = cell.traffic(args.rehearse_cpu)
    config = serve.serving_config(ctx)
    for item in args.set:
        key, value = item.split("=", 1)
        config[key] = json.loads(value)
    server = serve.Server(ctx, config)
    serve.hold_to_cpu(traffic)
    ramp_s = float(traffic["ramp_s"])
    lo, hi = ramp_s, ramp_s + args.seconds
    with server:
        server.wait_healthy()
        server.warm_up()
        print("after warm-up:", json.dumps(server.device("after_warm_up")),
              flush=True)
        for rate in (float(r) for r in args.rates.split(",")):
            schedule = serve.ramp_and_window(dict(traffic, rate_rps=rate),
                                             args.seed, ramp_s, args.seconds)
            marks = {}
            server.offer(schedule, [
                (lo, lambda: marks.update(open=server.scrape())),
                (hi, lambda: marks.update(close=server.scrape()))])
            nums = serve.window_numbers(schedule, lo, hi, server.deadline_s)
            lat = nums["latencies_ms"]
            wait = stats.histogram_delta(
                marks["open"].get("hbnlp_serve_queue_wait_seconds"),
                marks["close"]["hbnlp_serve_queue_wait_seconds"])
            late = traffic_mod.lateness_ms(schedule)
            memory = server.device("after_rate")["memory"][0]
            print(json.dumps({
                "rate_rps": rate, "requests_in_window": len(lat),
                "offered_tokens_per_sec": sum(
                    r.new_tokens for r in nums["sample"]) / args.seconds,
                "received_tokens_per_sec": nums["received_tokens_per_sec"],
                "answered_tokens_per_sec": nums["answered_tokens_per_sec"],
                "latency_p50_ms": stats.percentile(lat, 50),
                "latency_p95_ms": stats.percentile(lat, 95),
                "failed": sum(not r.ok() for r in schedule),
                "unanswered_at_close": sum(
                    1 for r in schedule
                    if r.due_s < hi and (r.done_s is None or r.done_s >= hi)),
                "drain_s": max(r.done_s for r in schedule if r.done_s) - hi,
                "server_queue_wait_mean_ms":
                    1e3 * wait["sum"] / max(wait["count"], 1),
                "slots_occupied_at_close":
                    marks["close"].get("hbnlp_serve_slots_occupied"),
                "lateness_max_ms": max(late),
                "bytes_in_use": memory.get("bytes_in_use"),
                "peak_bytes_in_use": memory.get("peak_bytes_in_use"),
                "bytes_limit": memory.get("bytes_limit")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
