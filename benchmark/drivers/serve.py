"""Traffic kind ``serve``: open-loop requests against the program's server.

The server is the program's own ``main.py --run_mode web_api`` (started
through ``serve_child.py``, which adds nothing but an idle command thread),
initialised from seeded weights because ``model_path`` holds no checkpoint.
This process never touches the chip: it holds jax to the CPU, builds the
same seeded weights for the reference while the server starts, generates
the load, and reads the server's ``/metrics``.

Timeline: spawn -> ``/health`` ok (``init_s``) -> warm-up requests that
build the engine's chunk programs (``compile_s``) -> a canary request alone
-> the schedule (``lib/traffic.py``): ``ramp_s`` seconds of load that is
sent but not judged, so the slot pool and the queue are in their steady
state when the window opens, then ``--seconds`` of judged load -> the
replies still owed -> device facts, SIGTERM, the reference check.
``setup_s`` runs from process start to the window's opening.

Judged are the requests DUE inside the window: latency from the due time to
the reply, a failed or refused request at the deadline; and the new tokens
of every reply received inside the window over its length.
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import threading
import time
import typing
import urllib.error
import urllib.request

from ..lib import procs, stats, traffic as traffic_mod
from ..lib.cell import ROOT
from ..lib.result import NoAccelerator, Result, footprint
from ..trace.reduce import newest_xplane

HOST, PORT = "127.0.0.1", 62220     # infer/rest_api.py DEFAULT_PORT
HISTOGRAMS = ("hbnlp_serve_queue_wait_seconds", "hbnlp_serve_ttft_seconds",
              "hbnlp_serve_itl_seconds", "hbnlp_serve_queue_age_seconds",
              "hbnlp_serve_slot_residency_seconds")


def _get(path: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(f"http://{HOST}:{PORT}{path}",
                                timeout=timeout) as resp:
        return resp.read()


def _await_file(path: str, timeout: float) -> dict:
    deadline = time.monotonic() + timeout
    while not os.path.exists(path):
        if time.monotonic() > deadline:
            raise TimeoutError(f"the server never wrote {path}")
        time.sleep(0.05)
    with open(path) as f:
        return json.load(f)


class Server:
    """One life of the program's server: spawn, wait until healthy, warm
    the engine's programs, talk to its command thread, stop all of it."""

    def __init__(self, ctx, config: dict):
        self.ctx, self.config = ctx, config
        self.child_env = dict(os.environ)
        out = ctx.out_dir
        self.log_path = os.path.join(out, "server.log")
        self.pipe = os.path.join(out, "commands.pipe")
        self.model_json = os.path.join(out, "model.json")
        self.cache_dir = self.child_env.get("JAX_COMPILATION_CACHE_DIR") \
            or os.path.join(ROOT, ".jax_cache")
        self.deadline_s = float(config["serve_request_deadline_s"])
        self.clients = int(ctx.cell.traffic(ctx.rehearsal)["clients"])
        self.proc = None
        self.t_spawn = self.t_health = self.t_warm = None
        self.engine: dict = {}

    def cache_entries(self) -> int:
        try:
            return sum(1 for f in os.listdir(self.cache_dir)
                       if f.endswith("-cache"))
        except OSError:
            return 0

    def __enter__(self):
        run_dir = self.config["model_path"]
        shutil.rmtree(run_dir, ignore_errors=True)
        os.makedirs(run_dir)
        with open(self.model_json, "w") as f:
            json.dump(self.config, f, indent=1)
        if os.path.exists(self.pipe):
            os.remove(self.pipe)
        os.mkfifo(self.pipe)
        self.t_spawn = time.monotonic()
        self.proc = procs.spawn(
            [sys.executable, os.path.join(ROOT, "benchmark", "drivers",
                                          "serve_child.py"),
             self.pipe, self.model_json],
            self.log_path, cwd=ROOT, env=self.child_env)
        return self

    def __exit__(self, *exc):
        rc = procs.stop_session(self.proc)
        self.ctx.log(f"server: exit code {rc}, session empty: "
                     f"{not procs.session_members(self.proc.pid)}")
        os.remove(self.pipe)

    def command(self, **cmd) -> None:
        with open(self.pipe, "w") as f:
            f.write(json.dumps(cmd) + "\n")

    def device(self, tag: str) -> dict:
        """jax's devices and their memory, as the server's process sees
        them."""
        path = os.path.join(self.ctx.out_dir, f"device_{tag}.json")
        if os.path.exists(path):
            os.remove(path)
        self.command(cmd="device", out=path)
        return _await_file(path, 30)

    def scrape(self) -> dict:
        return stats.parse_metrics(_get("/metrics").decode())

    def wait_healthy(self) -> None:
        """Until ``/health`` answers and names the continuous engine, on
        the chips the cell asks for."""
        health = None
        while health is None:
            if self.proc.poll() is not None:
                raise RuntimeError(
                    f"the server exited {self.proc.returncode} before "
                    f"serving:\n" + procs.tail(self.log_path, 40))
            if time.monotonic() - self.t_spawn > 900:
                raise RuntimeError("the server was not healthy after 900 s:"
                                   "\n" + procs.tail(self.log_path, 40))
            try:
                health = json.loads(_get("/health", timeout=5))
            except (urllib.error.URLError, OSError, ValueError):
                time.sleep(0.5)
        self.t_health = time.monotonic()
        self.engine = health.get("engine") or {}
        self.ctx.log(f"server healthy after "
                     f"{self.t_health - self.t_spawn:.2f}s: engine "
                     f"{self.engine}")
        if self.engine.get("mode") != "continuous":
            raise RuntimeError(f"/health does not name the continuous "
                               f"engine: {health}")
        found = self.device("at_health")
        cell = self.ctx.cell
        if (found["platform"] != "tpu" and not self.ctx.rehearsal) \
                or found["count"] != cell.chips:
            raise NoAccelerator(
                f"cell {cell.name} needs {cell.chips} TPU chip(s); the "
                f"server found {found['count']} x {found['platform']!r}")

    def warm_up(self) -> None:
        """The engine builds its three chunk programs on first use: a
        request spanning two chunks (init, then plain), then one admitted
        into the live pool (admit) — as ``chip_smoke.py`` does."""
        seq = int(self.config["sequence_length"])
        for i, (prompt, new) in enumerate(((8, min(80, seq - 16)), (5, 4))):
            req = traffic_mod.Request(-1 - i, 0.0, list(range(prompt)), new)
            traffic_mod.post_completion(HOST, PORT, req, 600.0)
            if not req.ok():
                raise RuntimeError(
                    f"warm-up request failed: {req.status} {req.error}\n"
                    + procs.tail(self.log_path, 40))
        self.t_warm = time.monotonic()

    def offer(self, schedule, at: typing.Sequence[typing.Tuple[float,
              typing.Callable[[], None]]] = ()) -> float:
        """Run the open loop over ``schedule``; ``at`` lists ``(seconds
        from the origin, callable)`` pairs run on timer threads (scrapes,
        trace start and stop).  Returns the origin."""
        origin = time.monotonic() + 0.2
        timers = [threading.Timer(origin + when - time.monotonic(), fn)
                  for when, fn in at]
        for t in timers:
            t.start()
        traffic_mod.run_open_loop(HOST, PORT, schedule, self.deadline_s,
                                  self.clients, origin)
        for t in timers:
            t.join(timeout=120)
        return origin


def ramp_and_window(traffic: dict, seed: int, ramp_s: float, seconds: float):
    """The schedule of a run: ``ramp_s`` seconds of load that is sent but
    not judged, then the window; each part with its own fixed count and
    lengths."""
    return traffic_mod.make_schedule(traffic, seed, ramp_s) \
        + traffic_mod.make_schedule(traffic, seed, seconds, offset_s=ramp_s,
                                    part=1)


def window_numbers(schedule, lo: float, hi: float, deadline_s: float,
                   skip=()) -> dict:
    """Of the requests DUE in ``[lo, hi)``: latency from the due time (a
    failed one at the deadline) and the new tokens of those answered before
    their deadline, per second of window — at a rate the server sustains
    this is the offered load, and every failed request lowers it.  Beside
    it the new tokens of all replies RECEIVED inside the window per
    second: the capacity number of a cell above the knee; below the knee it
    swings with where the chunk boundaries fall (replies leave the server
    in bursts, one burst a chunk)."""
    sample = [r for r in schedule if lo <= r.due_s < hi and r not in skip]
    latencies = [((r.done_s - r.due_s) if r.ok() else deadline_s) * 1e3
                 for r in sample]
    received = sum(len(r.tokens) - len(r.prompt) for r in schedule
                   if r.ok() and lo <= r.done_s < hi)
    answered = sum(len(r.tokens) - len(r.prompt) for r in sample if r.ok())
    return {"sample": sample, "latencies_ms": latencies,
            "received_tokens_per_sec": received / (hi - lo),
            "answered_tokens_per_sec": answered / (hi - lo)}


class _Weights(threading.Thread):
    """The same seeded weights the server initialises, built here on the
    host for the reference (numpy, in a thread: it releases the GIL)."""

    def __init__(self, config: dict):
        super().__init__(daemon=True, name="bench-reference-weights")
        self.config, self.variables, self.error = config, None, None

    def run(self):
        try:
            import numpy as np
            from homebrewnlp_tpu.config import ModelParameter
            from homebrewnlp_tpu.model import Model
            params = ModelParameter(dict(self.config), train=False,
                                    train_batch_size=1)
            seq = params.sequence_length // params.token_patch_size
            zeros = np.zeros((1, seq, params.token_patch_size), np.int32)
            self.variables = Model(params).init(
                {"token_x": zeros, "token_y": zeros.copy()})
        except Exception as exc:  # noqa: BLE001 — re-raised by the caller
            self.error = exc


def serving_config(ctx) -> dict:
    config = ctx.cell.model_config(ctx.rehearsal)
    config.update(data_seed=int(ctx.seed), dataset_configs=[],
                  model_path=os.path.join(ctx.out_dir, "run"))
    return config


def hold_to_cpu(traffic: dict) -> None:
    """This process stays off the chip (the server's environment was copied
    before); BLAS threads are capped so that the weights thread leaves the
    server's own start-up its cores."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("OPENBLAS_NUM_THREADS",
                          str(traffic["reference_threads"]))


def run(ctx) -> Result:
    cell, log = ctx.cell, ctx.log
    traffic = cell.traffic(ctx.rehearsal)
    config = serving_config(ctx)
    server = Server(ctx, config)          # copies the environment first
    hold_to_cpu(traffic)
    cache_at_start = server.cache_entries()
    weights = _Weights(config)
    with server:
        weights.start()
        server.wait_healthy()
        server.warm_up()
        # the canary: the mix's median lengths, its tokens from the seed
        import numpy as np
        canary = traffic_mod.Request(
            -3, 0.0, np.random.default_rng([int(ctx.seed), 9]).integers(
                0, int(traffic["vocab"]),
                int(traffic["prompt_tokens"]["median"])).tolist(),
            int(traffic["new_tokens"]["median"]))
        traffic_mod.post_completion(HOST, PORT, canary, server.deadline_s)
        log(f"warm-up {server.t_warm - server.t_health:.2f}s; canary alone: "
            f"status {canary.status}, {len(canary.tokens or [])} tokens")
        before = server.device("after_warm_up")

        # ---- the schedule: ramp + window, the canary again mid-window
        ramp_s = float(traffic["ramp_s"])
        lo, hi = ramp_s, ramp_s + ctx.seconds
        schedule = ramp_and_window(traffic, ctx.seed, ramp_s, ctx.seconds)
        again = traffic_mod.Request(-4, (lo + hi) / 2, list(canary.prompt),
                                    canary.new_tokens)
        schedule = sorted(schedule + [again], key=lambda r: r.due_s)
        marks: dict = {}
        trace_dir = os.path.join(ctx.out_dir, "trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_done = os.path.join(ctx.out_dir, "trace_done.json")
        if os.path.exists(trace_done):
            os.remove(trace_done)

        def at_open():
            marks["open"] = (server.scrape(), server.cache_entries())
            if ctx.trace:
                server.command(cmd="trace_start", dir=trace_dir)

        def at_close():
            marks["close"] = (server.scrape(), server.cache_entries())

        at = [(lo, at_open), (hi, at_close)]
        if ctx.trace:
            at.append((lo + float(traffic["trace_seconds"]),
                       lambda: server.command(cmd="trace_stop",
                                              out=trace_done)))
        origin = server.offer(schedule, at)
        if ctx.trace:
            _await_file(trace_done, 120)
        after = server.device("after_window")
        health_after = json.loads(_get("/health", timeout=5))

    numbers = window_numbers(schedule, lo, hi, server.deadline_s,
                             skip=(again,))
    sample, latencies = numbers["sample"], numbers["latencies_ms"]
    judged = sample + [again]
    late = traffic_mod.lateness_ms(schedule)
    with open(os.path.join(ctx.out_dir, "requests.jsonl"), "w") as f:
        for r in schedule:
            f.write(json.dumps({
                "index": r.index, "due_s": r.due_s, "sent_s": r.sent_s,
                "done_s": r.done_s, "status": r.status, "error": r.error,
                "prompt_tokens": len(r.prompt), "new_tokens": r.new_tokens,
                "judged": lo <= r.due_s < hi}) + "\n")
    log(f"schedule: {len(schedule)} requests ({len(sample)} due in the "
        f"window, {sum(not r.ok() for r in schedule)} failed); generator "
        f"lateness p50 {stats.median(late):.3f} ms, max {max(late):.3f} ms")
    (open_metrics, cache_open), (close_metrics, cache_close) = \
        marks["open"], marks["close"]
    histograms = {
        name: stats.histogram_delta(open_metrics.get(name),
                                    close_metrics[name])
        for name in HISTOGRAMS if name in close_metrics}

    def well_formed(r):
        return (r.ok() and len(r.tokens) == len(r.prompt) + r.new_tokens
                and r.tokens[:len(r.prompt)] == r.prompt
                and all(isinstance(t, int) and 0 <= t < config["vocab_size"]
                        for t in r.tokens))
    checks = {
        "replies_well_formed": all(well_formed(r) for r in judged
                                   if r.ok()),
        "canary_identical": canary.ok() and again.ok()
        and canary.tokens == again.tokens,
        "compiles_in_window": cache_close - cache_open,
        "no_compile_in_window": cache_close == cache_open,
        "healthy_after": health_after.get("status") == "ok"
        and not health_after.get("decode_failures"),
        "requests_in_window": len(sample),
    }
    weights.join(timeout=600)
    if weights.error is not None or weights.variables is None:
        raise RuntimeError(f"the reference's weights were not built: "
                           f"{weights.error!r}")
    checks.update(_reference_check(
        ctx, config, weights.variables,
        [r for r in [again] + sample if well_formed(r)][:2]))
    correct = all(checks[k] for k in (
        "replies_well_formed", "canary_identical", "no_compile_in_window",
        "healthy_after", "served_tokens_near_reference_argmax"))

    peak, limit = footprint(after["memory"])
    return Result(
        end_to_end={
            "serve_latency_p50_ms": stats.percentile(latencies, 50),
            "serve_latency_p90_ms": stats.percentile(latencies, 90),
            "serve_latency_p95_ms": stats.percentile(latencies, 95),
            "serve_tokens_per_sec": numbers["answered_tokens_per_sec"],
            "serve_received_tokens_per_sec":
                numbers["received_tokens_per_sec"],
            "setup_s": origin + lo - ctx.t_start},
        correct=correct, checks=checks, attempted=len(judged),
        failed=sum(not r.ok() for r in judged),
        device={"platform": after["platform"], "kind": after["kind"],
                "count": after["count"], "memory_peak_bytes": int(peak)},
        spans={"init_s": server.t_health - server.t_spawn,
               "compile_s": server.t_warm - server.t_health,
               "ramp_s": ramp_s, "window_s": ctx.seconds},
        counters={"histograms": histograms,
                  "memory_limit_bytes": int(limit),
                  "bytes_in_use_after_warm_up": [
                      m.get("bytes_in_use") for m in before["memory"]],
                  "bytes_in_use_after_window": [
                      m.get("bytes_in_use") for m in after["memory"]],
                  "cache_entries": [cache_at_start, cache_open, cache_close],
                  "slots_occupied_at_close":
                      close_metrics.get("hbnlp_serve_slots_occupied"),
                  "lateness_p50_ms": stats.median(late),
                  "lateness_max_ms": max(late)},
        trace_path=newest_xplane(trace_dir), trace_window=None,
        trace_spans=())


def _reference_check(ctx, config, variables, requests) -> dict:
    """Teacher-force the completed requests through the plain reference:
    every served token's reference logit has to be within the tolerance of
    that position's largest (logits, not tokens: with seeded weights the
    largest logit changes on rounding)."""
    import numpy as np

    from ..lib.cell import load_reference
    ref = load_reference(ctx.cell.config_name)
    seq = int(config["sequence_length"])
    tolerance = float(ctx.cell.spec["correct"]["logit_tolerance"])
    rows = np.zeros((len(requests), seq), np.int32)
    for i, r in enumerate(requests):
        rows[i, :len(r.tokens)] = r.tokens
    t0 = time.monotonic()
    logits = np.asarray(ref.forward(variables, rows, config))
    worst, scale, agree, total = 0.0, float(np.max(np.abs(logits))), 0, 0
    for i, r in enumerate(requests):
        for pos in range(len(r.prompt), len(r.tokens)):
            row = logits[i, pos - 1]
            worst = max(worst, float(np.max(row) - row[r.tokens[pos]]))
            agree += int(np.argmax(row)) == r.tokens[pos]
            total += 1
    with open(os.path.join(ctx.out_dir, "reference_check.json"), "w") as f:
        json.dump([{"prompt_tokens": len(r.prompt), "served": r.tokens,
                    "reference_argmax": np.argmax(
                        logits[i, :len(r.tokens) - 1], axis=-1).tolist()}
                   for i, r in enumerate(requests)], f)
    ctx.log(f"reference: {len(requests)} requests teacher-forced in "
            f"{time.monotonic() - t0:.2f}s; largest (max logit - served "
            f"token's logit) / max|logit| = {worst / scale:.6f} "
            f"(tolerance {tolerance}); {agree} of {total} served tokens ARE "
            f"the reference's argmax")
    return {"served_logit_gap": worst / scale,
            "served_tokens_near_reference_argmax":
                len(requests) > 0 and worst / scale <= tolerance}
