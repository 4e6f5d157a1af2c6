#!/usr/bin/env python3
"""Serving traffic generator: batch-to-completion vs continuous batching.

Drives the REAL REST path — ``rest_api.serve`` with its isolated device
loop, HTTP child, Manager IPC, admission control — with a reproducible
mixed-length workload (short and long prompts x short and long responses,
the regime where batch-to-completion pins a whole co-batch on its longest
row), in two generator modes per engine:

* **closed loop** — C workers each firing its next request the moment the
  previous answer lands (saturation throughput), then
* **open loop** — seeded-exponential interarrivals at a target rate, each
  request on its own thread (latency under a Poisson-ish load, the number
  p99 TTFT is about).

Per engine it reports client-side tokens/sec + request outcomes and the
server-side p50/p99 TTFT + ITL scraped from ``/metrics`` (the engine
records TTFT per slot event, the batch path per stepped-loop hook — the
bench config forces ``decode_loop=stepped`` so both sides report), and
writes a BENCH_*-style row to ``BENCH_SERVING.json``.

Acceptance (ISSUE 7): on the CPU backend the continuous engine sustains
>= 1.5x the batch engine's closed-loop tokens/sec at mixed lengths with a
lower open-loop p99 TTFT; the exit code enforces it under ``--check``.

Fault schedules: ``--latency I:SEC[,I:SEC...]`` wraps the interface in
``utils.fault_injection.FaultyInterface`` (the PR 3 schedules) — decode
call I sleeps SEC first.  The schedules fire on ``complete_tokens*`` calls,
i.e. the BATCH engine's decode path (the continuous engine drives the model
directly); use them to reproduce deadline/429 behavior under a stalling
batch decode.

CPU-scale model by default (harness-size mixer, seq 64); pass a config
JSON via ``--config`` to run a real checkpoint's shape instead.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import socket
import sys
import threading
import time
import urllib.error
import urllib.request

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

#: harness-scale serving model: small enough that one decode iteration is
#: milliseconds on CPU, deep/wide enough that the slot pool is a real
#: multi-leaf cache pytree (depth-stacked KV + int8-composable layout)
BENCH_CONFIG = {
    "model_mode": "gpt", "use_video": False, "use_language": True,
    "sequence_length": 64, "features_per_head": 16, "heads": 2,
    "depth": 2, "train_batch_size": 1, "vocab_size": 256,
    "group_linear_factor": 2,
    "intermediate_feed_forward_multiplier_multiplier": 0.5,
    "memory_reduction_strategy": "none",
    "block_config": [
        {"layer": ["norm-shift-scale-features-group",
                   "bottleneck_group_linear-in:relu-mid:relu-mid:norm-mid:"
                   "shift-mid:scale-mid:features"]},
        {"layer": ["norm-shift-scale-features-group",
                   "attention-biased_attention_map-absolute-input_as_value-"
                   "shared"]}],
    # the stepped loop on BOTH engines: it is what reports TTFT/ITL on the
    # batch path, and fine chunks are what let the continuous engine
    # recycle finished slots quickly (chunk boundaries = scheduling points)
    "decode_loop": "stepped", "decode_chunk_tokens": 4,
    "serve_prefill_chunk_tokens": 8,
    "serve_queue_limit": 256, "serve_request_deadline_s": 120.0,
    "model_path": "/tmp/bench_serving",
}

#: mixed request classes (prompt_tokens, max_tokens): the short/long mix
#: that makes batch-to-completion pay head-of-line blocking
WORKLOAD = ((3, 4), (5, 8), (2, 16), (6, 48), (4, 4), (3, 32))

# ---- speculative A/B (--spec; docs/SERVING.md 'Speculative decoding') ------
#
# Acceptance rate is the whole economics of spec decoding, and a RANDOM
# target is the one regime where no cheap draft can exist: an untrained
# full-width model is an incompressible random function, so a narrow
# draft predicts nothing (measured: 15-19% argmax agreement even after
# distillation).  Production pairs work because BOTH models are trained on
# the same distribution; the A/B reproduces exactly that: a tiny
# deterministic language (a fixed random permutation map over a 32-symbol
# alphabet — learnable to ~100% by both shapes in seconds of CPU
# training), the full-size target and the shallow/narrow draft each
# trained on it, and the serving workload drawn from the same
# distribution.  The measured acceptance rate is scraped from /metrics
# and recorded in the row — the speedup claim is "at THIS acceptance",
# not a universal constant; a workload the draft cannot predict
# self-disables via spec_min_accept_rate (tests pin that path).

#: the spec A/B language: alphabet size and the permutation seed
SPEC_LANG_MOD = 32
SPEC_LANG_SEED = 1234

#: target shape for the A/B: wide enough that decode steps (not HTTP/IPC
#: plumbing) dominate the closed-loop wall — at the default harness width
#: both engines saturate the request path and the A/B measures nothing
SPEC_TARGET_OVERRIDES = {"features_per_head": 64, "sequence_length": 96}

#: the draft: quarter width AND eighth depth (ROADMAP's
#: "shallow/quarter-width draft" — on an op-dispatch-bound CPU rig only
#: depth cuts per-step cost; on silicon the width cut is the byte-ratio
#: lever).  vocab_weight_factorization raised so the factorized embedding
#: keeps a non-degenerate intermediate at this width
SPEC_DRAFT_OVERRIDES = {"features_per_head": 16, "depth": 1,
                        "vocab_weight_factorization": 0.5,
                        "sequence_length": 96}

#: (steps, lr) phases per model (multi-phase supported — each phase
#: recompiles the step at its lr).  Measured: these budgets take both
#: models to ~1.0 argmax accuracy on the permutation language (half the
#: steps leaves the draft at ~0.79 and the A/B acceptance under water)
SPEC_TRAIN_PHASES = ((1400, 3e-3),)
SPEC_DRAFT_TRAIN_PHASES = ((3000, 3e-3),)

#: --spec request classes (prompt_tokens, max_tokens): longer responses
#: than WORKLOAD so the decode path, not per-request HTTP overhead, is
#: what the two engines differ on
SPEC_WORKLOAD = ((3, 80), (5, 48), (2, 88), (6, 32), (4, 64), (3, 88))


def _spec_perm():
    import numpy as np
    return np.random.default_rng(SPEC_LANG_SEED).permutation(SPEC_LANG_MOD)


def _spec_rows(perm, rng, n, seq):
    """``n`` on-manifold sequences: a random start symbol walking the
    permutation orbit."""
    import numpy as np
    rows = np.zeros((n, seq), np.int64)
    rows[:, 0] = rng.integers(0, len(perm), n)
    for t in range(1, seq):
        rows[:, t] = perm[rows[:, t - 1]]
    return rows.astype(np.int32)


def _train_bench_model(cfg_over, phases, perm, seed=0, bt=16):
    """Train one bench-scale model on the permutation language; returns
    (params, model, variables, final_loss)."""
    import numpy as np
    import jax.numpy as jnp
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.train import Trainer

    cfg = dict(BENCH_CONFIG)
    cfg.update(optimizer="adam-learning_rate", learning_rate=phases[0][1],
               warmup_steps=0, train_steps=10 ** 6, train_batch_size=bt,
               data_seed=seed)
    cfg.update(cfg_over)
    params = ModelParameter(cfg)
    model = Model(params)
    rng = np.random.default_rng(seed)
    seq = params.sequence_length

    def batch():
        rows = _spec_rows(perm, rng, bt, seq)
        return {"token_x": jnp.asarray(rows[:, :, None]),
                "token_y": jnp.asarray(np.roll(rows, -1, 1)[:, :, None])}

    trainer = Trainer(params, model)
    state = trainer.init_state(batch())
    metrics = {"loss": 0.0}
    for steps, lr in phases:
        params.learning_rate = lr
        # the jitted step bakes the learning rate as a trace-time constant
        # (optim/learning_rate.py); drop the cached step fn so each phase
        # actually recompiles at ITS lr — without this the anneal is a
        # silent no-op and phase 2 trains at phase 1's rate
        trainer._step_fn = None
        for _ in range(steps):
            state, metrics = trainer.step(state, batch())
    params.train = False
    variables = {k: jnp.asarray(v) for k, v in state.variables.items()}
    return params, model, variables, float(metrics["loss"])


def _build_spec_pair():
    """(target InterfaceWrapper, draft triple, alignment report): the
    trained full-width target + trained quarter-width draft the --spec A/B
    serves, with their measured teacher-forced argmax agreement."""
    import numpy as np
    import jax.numpy as jnp
    import time as _time
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.infer.interface import InterfaceWrapper
    from homebrewnlp_tpu.model import Model

    perm = _spec_perm()
    t0 = _time.monotonic()
    tparams, tmodel, tvars, tloss = _train_bench_model(
        dict(SPEC_TARGET_OVERRIDES,
             model_path="/tmp/bench_serving_spec_target"),
        SPEC_TRAIN_PHASES, perm, seed=0)
    dparams, dmodel, dvars, dloss = _train_bench_model(
        dict(SPEC_DRAFT_OVERRIDES,
             model_path="/tmp/bench_serving_spec_draft"),
        SPEC_DRAFT_TRAIN_PHASES, perm, seed=1)
    train_s = _time.monotonic() - t0

    # teacher-forced argmax agreement on fresh on-manifold rows — the
    # acceptance ceiling the serving run should approach
    rng = np.random.default_rng(99)
    rows = _spec_rows(perm, rng, 48, tparams.sequence_length)

    def preds(model, params, variables):
        from homebrewnlp_tpu.infer.interface import model_width_view
        out = []
        bt = 16
        pw, mw = model_width_view(params, model, bt)
        for lo in range(0, len(rows), bt):
            chunk = rows[lo:lo + bt]
            info = mw.apply(variables,
                            {"token_x": jnp.asarray(chunk[:, :, None]),
                             "token_y": jnp.asarray(chunk[:, :, None])})
            out.append(np.asarray(info.token_out.data,
                                  np.float32)[:, :, 0].argmax(-1))
        return np.concatenate(out)

    tp, dp = preds(tmodel, tparams, tvars), preds(dmodel, dparams, dvars)
    truth = np.roll(rows, -1, 1)
    gen = (slice(None), slice(1, -1))
    report = {
        "language": f"permutation map, {SPEC_LANG_MOD} symbols",
        "train_s": round(train_s, 1),
        "target_loss": round(tloss, 4), "draft_loss": round(dloss, 4),
        "target_accuracy": round(float((tp[gen] == truth[gen]).mean()), 4),
        "draft_accuracy": round(float((dp[gen] == truth[gen]).mean()), 4),
        "teacher_forced_agreement": round(float((tp[gen] == dp[gen]).mean()),
                                          4),
    }
    return (InterfaceWrapper(tparams, tmodel, tvars),
            (dparams, dmodel, dvars), report)


def _build_interface(config_path=None, latency=None):
    import numpy as np
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.infer.interface import InterfaceWrapper
    from homebrewnlp_tpu.model import Model
    import jax.numpy as jnp

    cfg = dict(BENCH_CONFIG)
    if config_path:
        with open(config_path) as f:
            cfg = {**json.load(f), "decode_loop": "stepped"}
    params = ModelParameter(cfg)
    params.train = False
    model = Model(params)
    seq = params.sequence_dim.size
    tps = params.token_patch_dim.size
    zeros = np.zeros((1, seq, tps), np.int32)
    variables = {k: jnp.asarray(v)
                 for k, v in model.init({"token_x": zeros,
                                         "token_y": zeros}).items()}
    interface = InterfaceWrapper(params, model, variables)
    if latency:
        from homebrewnlp_tpu.utils.fault_injection import FaultyInterface
        interface = FaultyInterface(interface, latency=latency)
    return interface


def _spawn(interface, engine: str, slots: int, batch: int, spec_k: int = 8,
           block_tokens: int = 8, trace_dir=None):
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.infer import rest_api

    # "spec" is the continuous engine with draft-and-verify required (the
    # caller attaches interface.draft); "paged" is the continuous engine on
    # the KV block pool with kv_paging required; "spec_paged" composes BOTH
    # components (the Engine's spec_paged_chunk_step); any construction
    # failure must fail the A/B loudly, not silently measure a lesser engine
    serve_engine = ("continuous" if engine in ("spec", "paged", "spec_paged")
                    else engine)
    trace_over = {}
    if trace_dir:
        # --trace: per-request span export (docs/OBSERVABILITY.md 'Request
        # tracing') under a scratch model_path, so the per-hop breakdown
        # never writes into a real run directory
        trace_over = {"trace_requests": True, "model_path": str(trace_dir)}
    params = ModelParameter(interface.params,
                            serve_engine=serve_engine, serve_slots=slots,
                            serve_batch_size=batch,
                            kv_paging="on" if engine in ("paged",
                                                         "spec_paged")
                            else "off",
                            kv_block_tokens=block_tokens,
                            spec_decode="draft" if engine in ("spec",
                                                              "spec_paged")
                            else "off",
                            spec_draft_tokens=spec_k, **trace_over)
    params.train = False
    # /health's decode_path reads the INTERFACE's params (FaultyInterface
    # proxies); the spec knobs themselves ride the resolved `params`
    interface.params.serve_engine = serve_engine
    interface.params.spec_decode = params.spec_decode
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    stop = threading.Event()
    t = threading.Thread(target=rest_api.serve, args=(params, interface),
                         kwargs={"port": port, "isolate": True, "stop": stop},
                         daemon=True, name="bench-server")
    t.start()
    return port, stop, t


def _post(port, payload, timeout=180.0, headers=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/token_completion",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _wait_up(port, deadline_s=180.0):
    req = urllib.request.Request(f"http://127.0.0.1:{port}/health")
    t0 = time.monotonic()
    while True:
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                return json.loads(resp.read())
        except Exception:
            if time.monotonic() - t0 > deadline_s:
                raise
            time.sleep(0.25)


def _scrape_buckets(port):
    """Cumulative TTFT/ITL bucket counts from the /metrics exposition."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        text = resp.read().decode()
    out = {}
    for name in ("hbnlp_serve_ttft_seconds", "hbnlp_serve_itl_seconds"):
        pat = re.compile(rf'^{name}_bucket{{le="([^"]+)"}} (\d+)', re.M)
        pairs = sorted(
            (float("inf") if le == "+Inf" else float(le), int(c))
            for le, c in pat.findall(text))
        bounds = [b for b, _ in pairs if b != float("inf")]
        cum = [c for _, c in pairs]
        out[name] = (bounds,
                     [c - (cum[i - 1] if i else 0)
                      for i, c in enumerate(cum)])
    return out


def _scrape_values(port, names):
    """Plain gauge/counter samples (``name value`` lines) from /metrics."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        text = resp.read().decode()
    out = {}
    for name in names:
        m = re.search(rf"^{name} ([0-9.e+-]+)", text, re.M)
        out[name] = float(m.group(1)) if m else 0.0
    return out


def _scrape_spec(port):
    """The hbnlp_spec_* counters (cumulative) from /metrics."""
    v = _scrape_values(port, ("hbnlp_spec_drafted_tokens_total",
                              "hbnlp_spec_accepted_tokens_total",
                              "hbnlp_spec_state"))
    return {"drafted": v["hbnlp_spec_drafted_tokens_total"],
            "accepted": v["hbnlp_spec_accepted_tokens_total"],
            "state": v["hbnlp_spec_state"]}


def _quantiles(before, after):
    """p50/p99 of the TIMED window: per-bucket count delta between two
    scrapes — the warmup window's compile-dominated TTFTs must not ride
    the tail of the measured distribution."""
    from homebrewnlp_tpu.telemetry.registry import histogram_quantile
    out = {}
    for name, (bounds, counts_after) in after.items():
        counts_before = before.get(name, (bounds, [0] * len(counts_after)))[1]
        counts = [a - b for a, b in zip(counts_after, counts_before)]
        key = "ttft" if "ttft" in name else "itl"
        out[f"{key}_count"] = sum(counts)
        for q in (0.5, 0.99):
            out[f"{key}_p{int(q * 100)}"] = histogram_quantile(bounds,
                                                               counts, q)
    return out


class _Stats:
    def __init__(self):
        self.lock = threading.Lock()
        self.ok = 0
        self.errors = {}
        self.generated = 0

    def record(self, status, body, prompt_len):
        with self.lock:
            if status == 200:
                self.ok += 1
                self.generated += max(0, len(body.get("tokens", ()))
                                      - prompt_len)
            else:
                key = str(status)
                self.errors[key] = self.errors.get(key, 0) + 1


def _request_for(rng, i, orbit=None):
    classes = WORKLOAD if orbit is None else SPEC_WORKLOAD
    plen, mt = classes[i % len(classes)]
    if orbit is not None:
        # --spec A/B: on-manifold prompts (a walk of the trained
        # permutation language) so acceptance measures the aligned pair,
        # not out-of-distribution noise
        toks = [int(rng.integers(0, len(orbit)))]
        for _ in range(plen - 1):
            toks.append(int(orbit[toks[-1]]))
    else:
        toks = [int(x) for x in rng.integers(1, 255, plen)]
    return {"tokens": toks, "max_tokens": mt, "temperature": 0.0}, plen


def _closed_loop(port, rng, workers: int, per_worker: int, orbit=None,
                 trace_ids=None):
    stats = _Stats()
    # payloads pre-drawn on this thread: numpy Generators are not
    # thread-safe, and racy draw order would break --seed reproducibility
    payloads = [[_request_for(rng, w * per_worker + i, orbit=orbit)
                 for i in range(per_worker)] for w in range(workers)]

    def worker(w):
        from homebrewnlp_tpu.telemetry import tracectx
        for payload, plen in payloads[w]:
            headers = None
            if trace_ids is not None:
                # --trace: the CLIENT mints the id (header adoption at the
                # HTTP edge), so the per-hop files are findable afterwards
                tid = tracectx.new_trace_id()
                headers = {tracectx.TRACE_HEADER: tid}
            t_req = time.monotonic()
            try:
                status, body = _post(port, payload, headers=headers)
            except Exception:
                stats.record(599, {}, plen)
                continue
            stats.record(status, body, plen)
            if trace_ids is not None and status == 200:
                with stats.lock:
                    trace_ids.append((tid, time.monotonic() - t_req))

    t0 = time.monotonic()
    threads = [threading.Thread(target=worker, args=(w,), daemon=True,
                                name=f"bench-worker-{w}")
               for w in range(workers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    return stats, wall


def _open_loop(port, rng, rate_rps: float, duration_s: float, orbit=None):
    stats = _Stats()
    threads = []
    t0 = time.monotonic()
    i = 0
    while time.monotonic() - t0 < duration_s:
        payload, plen = _request_for(rng, i, orbit=orbit)
        i += 1

        def fire(payload=payload, plen=plen):
            try:
                status, body = _post(port, payload)
            except Exception:
                stats.record(599, {}, plen)
                return
            stats.record(status, body, plen)

        th = threading.Thread(target=fire, daemon=True,
                              name=f"bench-fire-{len(threads)}")
        th.start()
        threads.append(th)
        time.sleep(float(rng.exponential(1.0 / rate_rps)))
    for th in threads:
        th.join(timeout=180)
    wall = time.monotonic() - t0
    return stats, wall


def _hop_breakdown(trace_dir, trace_ids) -> dict:
    """p50/p99 per-hop seconds over the traced closed-loop requests:
    queue-wait / prefill / decode (+ kv-block-wait when paged), plus the
    client-measured dispatch overhead (client wall minus the in-engine
    request span).  Reads the per-request exports the tracer wrote under
    <trace_dir>/traces/.  The router-dispatch hop of a REPLICATED
    deployment lives in the router process's blackbox, not these files —
    merge it with ``scripts/forensics.py --trace <id>``."""
    import numpy as np
    per_hop: dict = {}
    dispatch_overhead = []
    found = 0
    for tid, wall in trace_ids:
        path = os.path.join(trace_dir, "traces", f"trace_{tid}.json")
        try:
            with open(path) as f:
                hops = json.load(f).get("hops") or {}
        except (OSError, ValueError):
            continue
        found += 1
        for key in ("queue_wait", "kv_block_wait", "prefill", "decode"):
            if key in hops:
                per_hop.setdefault(key, []).append(hops[key])
        if "request" in hops:
            dispatch_overhead.append(max(0.0, wall - hops["request"]))
    out = {"traced_requests": found}
    for key, vals in sorted(per_hop.items()):
        out[key] = {"p50": round(float(np.percentile(vals, 50)), 6),
                    "p99": round(float(np.percentile(vals, 99)), 6),
                    "n": len(vals)}
    if dispatch_overhead:
        out["dispatch"] = {
            "p50": round(float(np.percentile(dispatch_overhead, 50)), 6),
            "p99": round(float(np.percentile(dispatch_overhead, 99)), 6),
            "n": len(dispatch_overhead)}
    return out


def run_engine(engine: str, args, latency=None, spec_ctx=None) -> dict:
    import numpy as np
    orbit = None
    if spec_ctx is not None:
        interface, draft, orbit = (spec_ctx["interface"], spec_ctx["draft"],
                                   spec_ctx["orbit"])
        interface.draft = draft if engine == "spec" else None
    else:
        interface = _build_interface(args.config, latency=latency)
    trace_dir = None
    if getattr(args, "trace", False):
        import tempfile
        trace_dir = tempfile.mkdtemp(prefix=f"bench_trace_{engine}_")
    port, stop, t = _spawn(interface, engine, args.slots, args.batch,
                           spec_k=getattr(args, "spec_k", 8),
                           trace_dir=trace_dir)
    try:
        health = _wait_up(port)
        served = "continuous" if engine == "spec" else engine
        assert (health.get("engine") or {}).get("mode") == served, health
        if engine == "spec":
            spec_info = (health.get("engine") or {}).get("spec") or {}
            assert spec_info.get("enabled"), health
        # warmup: compile every program shape out of the timed window
        warm_rng = np.random.default_rng(7)
        for i in range(max(2, args.slots)):
            payload, _ = _request_for(warm_rng, i, orbit=orbit)
            _post(port, payload)
        # greedy bit-parity canary: the same request answers identically on
        # every engine (the --check gate compares across rows)
        canary, _ = _request_for(np.random.default_rng(1234), 3,
                                 orbit=orbit)
        canary_status, canary_body = _post(port, canary)
        rng = np.random.default_rng(args.seed)
        # the scrape merges the device loop's snapshot, published once per
        # loop turn — give it one idle poll to flush the warmup counts
        time.sleep(1.5)
        baseline = _scrape_buckets(port)
        spec_before = _scrape_spec(port) if engine == "spec" else None
        trace_ids = [] if trace_dir else None
        closed, closed_wall = _closed_loop(port, rng, args.concurrency,
                                           args.requests, orbit=orbit,
                                           trace_ids=trace_ids)
        open_stats, open_wall = _open_loop(port, rng, args.rate,
                                           args.duration, orbit=orbit)
        time.sleep(1.5)   # final snapshot publish
        q = _quantiles(baseline, _scrape_buckets(port))
        row = {
            "engine": engine,
            "canary": (canary_body.get("tokens")
                       if canary_status == 200 else None),
            "closed_loop": {
                "requests_ok": closed.ok, "errors": closed.errors,
                "generated_tokens": closed.generated,
                "wall_s": round(closed_wall, 3),
                "tokens_per_sec": round(closed.generated
                                        / max(closed_wall, 1e-9), 2),
            },
            "open_loop": {
                "rate_rps": args.rate, "requests_ok": open_stats.ok,
                "errors": open_stats.errors,
                "generated_tokens": open_stats.generated,
                "wall_s": round(open_wall, 3),
            },
            **{k: (round(v, 6) if isinstance(v, float) else v)
               for k, v in q.items()},
        }
        if engine == "spec":
            after = _scrape_spec(port)
            drafted = after["drafted"] - spec_before["drafted"]
            accepted = after["accepted"] - spec_before["accepted"]
            row["spec"] = {
                "drafted": int(drafted), "accepted": int(accepted),
                "accept_rate": round(accepted / max(drafted, 1.0), 4),
                "state": after["state"],
            }
        if trace_ids is not None:
            # per-hop latency anatomy of the closed-loop window (ISSUE 15
            # satellite): where a request's wall time actually went
            row["hops"] = _hop_breakdown(trace_dir, trace_ids)
            if engine == "batch" and not row["hops"]["traced_requests"]:
                # an explicit absence, not a zero that reads like a
                # collection failure
                row["hops"]["note"] = ("batch engine untraced — request "
                                       "tracing rides the continuous "
                                       "engine's hooks")
        return row
    finally:
        stop.set()
        t.join(timeout=30)


# ---- shared-prefix workload (--shared-prefix; docs/SERVING.md 'Paged KV') --
#
# The chat pattern paging + radix sharing exist for: every request opens
# with the same system prompt and diverges in a short tail.  The paged
# engine should (a) answer prefix-HIT requests with TTFT << a cold
# request's (prefill over the shared span is skipped — the blocks are
# referenced, not recomputed), (b) stay greedy-bit-identical to the plain
# continuous engine, and (c) show block occupancy tracking LIVE tokens,
# not slots x worst-case length.  TTFT is probed client-side with
# max_tokens=1 requests (end-to-end admission->first-token wall for the
# smallest possible decode), cold on a FRESH system prompt per trial, hit
# on tails diverging from an already-served one.

SHARED_SYS_TOKENS = 44          # shared system-prompt length (of seq 64)
SHARED_BLOCK_TOKENS = 4         # paging granularity for the workload
SHARED_TRIALS = 3
SHARED_HITS_PER_TRIAL = 3


def _shared_sysprompt(trial: int):
    import numpy as np
    rng = np.random.default_rng(1000 + trial)
    return [int(t) for t in rng.integers(1, 255, SHARED_SYS_TOKENS)]


def _timed_post(port, payload):
    t0 = time.monotonic()
    status, body = _post(port, payload)
    return time.monotonic() - t0, status, body


def run_shared_prefix(args) -> dict:
    import numpy as np
    interface = _build_interface(args.config)
    # greedy canary on the PLAIN continuous engine first: the paged
    # engine's answers must be bit-identical
    canary_payload = {"tokens": [3, 1, 4, 1, 5], "max_tokens": 8,
                     "temperature": 0.0}
    port, stop, t = _spawn(interface, "continuous", args.slots, args.batch)
    try:
        _wait_up(port)
        status, plain_canary = _post(port, canary_payload)
        assert status == 200, plain_canary
    finally:
        stop.set()
        t.join(timeout=30)
    port, stop, t = _spawn(interface, "paged", args.slots, args.batch,
                           block_tokens=SHARED_BLOCK_TOKENS)
    try:
        health = _wait_up(port)
        paging = (health.get("engine") or {}).get("paging") or {}
        assert paging.get("blocks_total"), health
        # warmup compiles every chunk-program shape out of the timed probes
        warm_rng = np.random.default_rng(7)
        for i in range(3):
            payload, _ = _request_for(warm_rng, i)
            _post(port, payload)
        status, paged_canary = _post(port, canary_payload)
        assert status == 200, paged_canary
        colds, hits = [], []
        for trial in range(SHARED_TRIALS):
            sysp = _shared_sysprompt(trial)
            dt, status, _ = _timed_post(
                port, {"tokens": sysp + [201, 202], "max_tokens": 1,
                       "temperature": 0.0})
            assert status == 200
            colds.append(dt)
            for j in range(SHARED_HITS_PER_TRIAL):
                dt, status, _ = _timed_post(
                    port, {"tokens": sysp + [210 + j], "max_tokens": 1,
                           "temperature": 0.0})
                assert status == 200
                hits.append(dt)
        time.sleep(1.5)  # device-loop snapshot publish
        kv = _scrape_values(port, (
            "hbnlp_kv_blocks_total", "hbnlp_kv_prefix_hit_tokens_total",
            "hbnlp_kv_prefix_hits_total", "hbnlp_kv_cow_copies_total"))
        # occupancy probe: sample the in-use gauge while long responses
        # decode — the live-token footprint, vs the slot engine's
        # slots x seq_blocks worst-case pinning
        peak = [0.0]
        done = threading.Event()

        def sample():
            while not done.is_set():
                try:
                    v = _scrape_values(port, ("hbnlp_kv_blocks_in_use",))
                    peak[0] = max(peak[0], v["hbnlp_kv_blocks_in_use"])
                except Exception:
                    pass
                time.sleep(0.15)

        sampler = threading.Thread(target=sample, daemon=True,
                                   name="bench-occupancy-sampler")
        sampler.start()
        occ_threads = [threading.Thread(
            target=_post, args=(port, {"tokens": [5 + i], "max_tokens": 16,
                                       "temperature": 0.0}), daemon=True,
            name=f"bench-occ-{i}")
            for i in range(args.slots)]
        for th in occ_threads:
            th.start()
        for th in occ_threads:
            th.join(timeout=180)
        time.sleep(1.6)  # one more scrape past the final chunk
        done.set()
        sampler.join(timeout=5)
        seq_blocks = 64 // SHARED_BLOCK_TOKENS  # BENCH_CONFIG sequence
        cold_med = sorted(colds)[len(colds) // 2]
        hit_med = sorted(hits)[len(hits) // 2]
        return {
            "mode": "shared_prefix",
            "sys_tokens": SHARED_SYS_TOKENS,
            "block_tokens": SHARED_BLOCK_TOKENS,
            "canary_parity": plain_canary.get("tokens")
            == paged_canary.get("tokens"),
            "cold_ttft_s": [round(v, 4) for v in colds],
            "hit_ttft_s": [round(v, 4) for v in hits],
            "cold_ttft_median_s": round(cold_med, 4),
            "hit_ttft_median_s": round(hit_med, 4),
            "hit_over_cold": round(hit_med / max(cold_med, 1e-9), 4),
            "prefix_hit_tokens": int(
                kv["hbnlp_kv_prefix_hit_tokens_total"]),
            "prefix_hits": int(kv["hbnlp_kv_prefix_hits_total"]),
            "occupancy": {
                "blocks_total": int(kv["hbnlp_kv_blocks_total"]),
                "peak_blocks_in_use": int(peak[0]),
                "slot_engine_equivalent_blocks": args.slots * seq_blocks,
            },
        }
    finally:
        stop.set()
        t.join(timeout=30)


# ---- composed spec-on-paged (--spec-paged; docs/SERVING.md 'Engine
# architecture') --------------------------------------------------------------
#
# The Engine's composition headline: spec-decode and paged KV were measured
# separately (the `spec` and `shared_prefix` rows) but refused to compose
# until the chunk-program registry made the carry composable
# (`spec_paged_chunk_step`).  This mode proves the win is MULTIPLICATIVE in
# ONE deployment: against the PLAIN continuous engine, the composed engine
# must deliver the draft-and-verify closed-loop tokens/sec speedup AND the
# prefix-hit TTFT collapse, while staying greedy-bit-identical.  Both the
# throughput window and the TTFT probes run against the SAME serving
# process — no per-feature deployments.

SPEC_PAGED_BLOCK_TOKENS = 8     # paging granularity (divides seq 96)
SPEC_PAGED_SYS_TOKENS = 64      # shared system-prompt length (8 full blocks)
SPEC_PAGED_TRIALS = 3
SPEC_PAGED_HITS_PER_TRIAL = 3


def _orbit_sysprompt(orbit, trial: int):
    """A shared system prompt ON the permutation manifold (an orbit walk
    from a per-trial start), so the composed deployment drafts at the
    trained pair's acceptance rate while the radix cache serves the shared
    span.  Distinct starts guarantee distinct first blocks (the radix key
    is the token sequence from the root), so each trial's first probe is
    genuinely cold."""
    toks = [(11 * trial + 5) % len(orbit)]
    for _ in range(SPEC_PAGED_SYS_TOKENS - 1):
        toks.append(int(orbit[toks[-1]]))
    return toks


def run_spec_paged(args) -> dict:
    import numpy as np
    interface, draft, align = _build_spec_pair()
    orbit = _spec_perm()
    canary_payload, _ = _request_for(np.random.default_rng(1234), 3,
                                     orbit=orbit)

    def warm_and_canary(port):
        warm_rng = np.random.default_rng(7)
        for i in range(max(2, args.slots)):
            payload, _ = _request_for(warm_rng, i, orbit=orbit)
            _post(port, payload)
        status, body = _post(port, canary_payload)
        assert status == 200, body
        return body

    # phase A: the PLAIN continuous engine — the baseline BOTH composed
    # components must beat together (draft detached so nothing drafts)
    interface.draft = None
    port, stop, t = _spawn(interface, "continuous", args.slots, args.batch)
    try:
        _wait_up(port)
        plain_canary = warm_and_canary(port)
        rng = np.random.default_rng(args.seed)
        plain_stats, plain_wall = _closed_loop(
            port, rng, args.concurrency, args.requests, orbit=orbit)
    finally:
        stop.set()
        t.join(timeout=30)

    # phase B: the composed spec_paged_chunk_step deployment
    interface.draft = draft
    port, stop, t = _spawn(interface, "spec_paged", args.slots, args.batch,
                           spec_k=args.spec_k,
                           block_tokens=SPEC_PAGED_BLOCK_TOKENS)
    try:
        health = _wait_up(port)
        einfo = health.get("engine") or {}
        # the composed deployment must BE the composed program — a
        # component-wise fallback here would silently measure a lesser
        # engine and void the row
        assert einfo.get("program") == "spec_paged_chunk_step", health
        assert (einfo.get("spec") or {}).get("enabled"), health
        assert (einfo.get("paging") or {}).get("blocks_total"), health
        comp_canary = warm_and_canary(port)
        time.sleep(1.5)  # device-loop snapshot publish
        spec_before = _scrape_spec(port)
        rng = np.random.default_rng(args.seed)
        comp_stats, comp_wall = _closed_loop(
            port, rng, args.concurrency, args.requests, orbit=orbit)
        # prefix-hit vs cold TTFT in the SAME deployment: a fresh shared
        # system prompt is cold; tails diverging off it hit its promoted
        # blocks.  Closed-loop prompts (2-6 tokens) never fill a block, so
        # they cannot pre-warm the probes.
        colds, hits = [], []
        for trial in range(SPEC_PAGED_TRIALS):
            sysp = _orbit_sysprompt(orbit, trial)
            nxt = int(orbit[sysp[-1]])   # the on-manifold next symbol
            dt, status, _ = _timed_post(
                port, {"tokens": sysp + [(nxt + 11) % len(orbit)],
                       "max_tokens": 1, "temperature": 0.0})
            assert status == 200
            colds.append(dt)
            for j in range(SPEC_PAGED_HITS_PER_TRIAL):
                dt, status, _ = _timed_post(
                    port, {"tokens": sysp + [(nxt + 1 + j) % len(orbit)],
                           "max_tokens": 1, "temperature": 0.0})
                assert status == 200
                hits.append(dt)
        time.sleep(1.5)  # device-loop snapshot publish
        spec_after = _scrape_spec(port)
        kv = _scrape_values(port, (
            "hbnlp_kv_blocks_total", "hbnlp_kv_prefix_hit_tokens_total",
            "hbnlp_kv_prefix_hits_total"))
    finally:
        stop.set()
        t.join(timeout=30)

    plain_tps = plain_stats.generated / max(plain_wall, 1e-9)
    comp_tps = comp_stats.generated / max(comp_wall, 1e-9)
    drafted = spec_after["drafted"] - spec_before["drafted"]
    accepted = spec_after["accepted"] - spec_before["accepted"]
    cold_med = sorted(colds)[len(colds) // 2]
    hit_med = sorted(hits)[len(hits) // 2]
    return {
        "mode": "spec_paged",
        "program": "spec_paged_chunk_step",
        "alignment": align,
        "spec_k": args.spec_k,
        "block_tokens": SPEC_PAGED_BLOCK_TOKENS,
        "sys_tokens": SPEC_PAGED_SYS_TOKENS,
        "canary_parity": (plain_canary.get("tokens")
                          == comp_canary.get("tokens")),
        "plain": {
            "requests_ok": plain_stats.ok, "errors": plain_stats.errors,
            "generated_tokens": plain_stats.generated,
            "wall_s": round(plain_wall, 3),
            "tokens_per_sec": round(plain_tps, 2),
        },
        "composed": {
            "requests_ok": comp_stats.ok, "errors": comp_stats.errors,
            "generated_tokens": comp_stats.generated,
            "wall_s": round(comp_wall, 3),
            "tokens_per_sec": round(comp_tps, 2),
        },
        "tokens_per_sec_speedup": round(comp_tps / max(plain_tps, 1e-9), 3),
        "spec": {
            "drafted": int(drafted), "accepted": int(accepted),
            "accept_rate": round(accepted / max(drafted, 1.0), 4),
            "state": spec_after["state"],
        },
        "cold_ttft_s": [round(v, 4) for v in colds],
        "hit_ttft_s": [round(v, 4) for v in hits],
        "cold_ttft_median_s": round(cold_med, 4),
        "hit_ttft_median_s": round(hit_med, 4),
        "hit_over_cold": round(hit_med / max(cold_med, 1e-9), 4),
        "prefix_hit_tokens": int(kv["hbnlp_kv_prefix_hit_tokens_total"]),
        "prefix_hits": int(kv["hbnlp_kv_prefix_hits_total"]),
        "blocks_total": int(kv["hbnlp_kv_blocks_total"]),
    }


# ---- multi-replica tier (--replicas N; docs/SERVING.md) ---------------------
#
# Aggregate tokens/sec should scale ~linearly in replicas.  This rig has
# ONE host core (the PR 10 bench_multihost caveat), so N real CPU-decoding
# replicas serialize on compute and CANNOT scale in wall time on this box
# — the committed curve therefore measures the TIER (router dispatch, per-
# replica serving stacks, IPC) with each replica's decode emulated as a
# DEVICE WAIT (a fixed sleep per decode call, the time a real accelerator
# would spend off-CPU), plus an honest real-model 1->2 datapoint with the
# rig caveat recorded.  On silicon the re-measure drops the emulation
# (device speed: not measured).

#: replica-bench model: tiny (compile + decode cost << the device wait)
REPLICA_OVERRIDES = {"sequence_length": 16, "features_per_head": 8,
                     "heads": 2, "depth": 1, "vocab_size": 64,
                     "serve_engine": "batch", "serve_batch_size": 4}
#: short requests (prompt, max_tokens) — each ~1 decode call
REPLICA_WORKLOAD = ((2, 4), (3, 6), (2, 8))
#: emulated device seconds per decode call
REPLICA_DEVICE_WAIT_S = 0.4


class _WaitInterface:
    """Device-wait emulation: every decode call sleeps ``wait_s`` first —
    the off-CPU accelerator time a CPU rig cannot reproduce.  Unlike
    FaultyInterface's per-index latency schedules this waits on EVERY
    call (a uniform device, not an injected stall)."""

    def __init__(self, inner, wait_s: float):
        self._inner = inner
        self._wait = float(wait_s)

    def complete_tokens(self, *a, **kw):
        time.sleep(self._wait)
        return self._inner.complete_tokens(*a, **kw)

    def complete_tokens_batch(self, *a, **kw):
        time.sleep(self._wait)
        return self._inner.complete_tokens_batch(*a, **kw)

    def complete(self, *a, **kw):
        time.sleep(self._wait)
        return self._inner.complete(*a, **kw)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def _replica_bench_main(cfg, port, index):
    """Replica subprocess body (spawn target — module-level so the spawn
    context can re-import it): build the bench interface, serve one
    isolated deployment, optionally under the device-wait emulation."""
    cfg = dict(cfg)
    wait = float(cfg.pop("_bench_wait_s", 0.0) or 0.0)
    import numpy as np
    import jax.numpy as jnp
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.distributed.replica_fleet import install_replica_stop
    from homebrewnlp_tpu.infer.interface import InterfaceWrapper
    from homebrewnlp_tpu.infer.rest_api import serve
    from homebrewnlp_tpu.model import Model

    stop = install_replica_stop()
    params = ModelParameter(cfg)
    params.train = False
    model = Model(params)
    seq = params.sequence_dim.size
    tps = params.token_patch_dim.size
    zeros = np.zeros((1, seq, tps), np.int32)
    variables = {k: jnp.asarray(v)
                 for k, v in model.init({"token_x": zeros,
                                         "token_y": zeros}).items()}
    interface = InterfaceWrapper(params, model, variables)
    if wait:
        interface = _WaitInterface(interface, wait)
    print(f"[replica {index}] bench replica on :{port}", flush=True)
    serve(params, interface, port=port, isolate=True, stop=stop)


def _replica_request(rng, i):
    plen, mt = REPLICA_WORKLOAD[i % len(REPLICA_WORKLOAD)]
    toks = [int(x) for x in rng.integers(1, 63, plen)]
    return {"tokens": toks, "max_tokens": mt, "temperature": 0.0}, plen


def _run_replica_point(n: int, wait_s: float, args) -> dict:
    """One point of the scaling curve: n replicas + router, closed loop."""
    import numpy as np
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.distributed.replica_fleet import ReplicaFleet
    from homebrewnlp_tpu.infer import rest_api
    from homebrewnlp_tpu.infer.router import Replica, Router
    from homebrewnlp_tpu.infer.serving_guard import HTTPStatusError

    cfg = {**BENCH_CONFIG, **REPLICA_OVERRIDES,
           "model_path": "/tmp/bench_serving_replica",
           "_bench_wait_s": wait_s}
    params = ModelParameter({k: v for k, v in cfg.items()
                             if not k.startswith("_")})
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        router_port = s.getsockname()[1]
    base = router_port + 1
    fleet = ReplicaFleet(params, n, base_port=base,
                         target=_replica_bench_main)
    fleet.cfg = dict(cfg)  # ride the bench-only _bench_wait_s key through
    router = Router([Replica(i, base + i) for i in range(n)],
                    affinity_tokens=0,  # pure least-loaded: the scaling
                    forward_timeout_s=300.0)  # curve, not cache locality

    def dispatch(path, body):
        if path == "/health":
            return router.health()
        if path == "/metrics":
            return {"_prometheus": router.metrics()}
        return router.forward(path, body)

    try:
        # non-daemonic replicas: start() under the finally that stops them
        fleet.start()
        threading.Thread(
            target=rest_api._run_http, name="bench-router-http",
            args=(router_port, ["/token_completion", "/health", "/metrics"],
                  dispatch, 1), daemon=True).start()
        deadline = time.monotonic() + 600
        while True:
            try:
                h = _wait_up(router_port, deadline_s=30)
                if all("health" in r for r in h.get("replicas", ())):
                    break
            except Exception:
                pass
            if time.monotonic() > deadline:
                raise TimeoutError("replica fleet never came up")
            time.sleep(1.0)
        # warmup: compile every replica's decode programs off the clock
        warm_rng = np.random.default_rng(7)
        for round_ in range(2):
            threads = []
            for i in range(n * 2):
                payload, _ = _replica_request(warm_rng, i)
                th = threading.Thread(target=_post,
                                      args=(router_port, payload),
                                      daemon=True,
                                      name=f"bench-warm-{i}")
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=300)
        stats = _Stats()
        rng = np.random.default_rng(args.seed)
        workers = max(2, 3 * n)
        per_worker = args.requests
        payloads = [[_replica_request(rng, w * per_worker + i)
                     for i in range(per_worker)] for w in range(workers)]

        def worker(w):
            for payload, plen in payloads[w]:
                try:
                    status, body = _post(router_port, payload, timeout=300)
                except Exception:
                    stats.record(599, {}, plen)
                    continue
                stats.record(status, body, plen)

        t0 = time.monotonic()
        threads = [threading.Thread(target=worker, args=(w,), daemon=True,
                                    name=f"bench-worker-{w}")
                   for w in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.monotonic() - t0
        return {"replicas": n, "requests_ok": stats.ok,
                "errors": stats.errors,
                "generated_tokens": stats.generated,
                "wall_s": round(wall, 3),
                "tokens_per_sec": round(stats.generated / max(wall, 1e-9),
                                        2),
                "workers": workers}
    finally:
        fleet.stop()


def run_replicas(args) -> dict:
    """The scaling sweep: 1 -> args.replicas doubling, device-wait
    emulated; plus a real-model 1->2 honesty datapoint."""
    ns = [1]
    while ns[-1] * 2 <= args.replicas:
        ns.append(ns[-1] * 2)
    if ns[-1] != args.replicas:
        ns.append(args.replicas)
    curve = []
    for n in ns:
        row = _run_replica_point(n, REPLICA_DEVICE_WAIT_S, args)
        print(json.dumps({"replica_point": row}), flush=True)
        curve.append(row)
    base = curve[0]["tokens_per_sec"]
    for row in curve:
        row["scaling_efficiency"] = round(
            row["tokens_per_sec"] / max(base * row["replicas"], 1e-9), 3)
    real = []
    for n in (1, 2):
        row = _run_replica_point(n, 0.0, args)
        print(json.dumps({"replica_real_point": row}), flush=True)
        real.append(row)
    real_base = real[0]["tokens_per_sec"]
    for row in real:
        row["scaling_efficiency"] = round(
            row["tokens_per_sec"] / max(real_base * row["replicas"], 1e-9),
            3)
    return {
        "mode": "replicas",
        "device_wait_s": REPLICA_DEVICE_WAIT_S,
        "host_cores": os.cpu_count(),
        "note": ("device-wait emulation: each decode call sleeps "
                 "device_wait_s (off-CPU accelerator time); this rig has "
                 f"{os.cpu_count()} host core(s), so real CPU decode "
                 "serializes across replicas — the 'real' rows record "
                 "that honestly, the emulated curve measures the tier; "
                 "device speed not measured"),
        "curve": curve,
        "real_model": real,
    }


# ---- disaggregated prefill/decode tier (--disagg; docs/SERVING.md
# 'Disaggregated tier') --------------------------------------------------------
#
# The ISSUE 19 headline: at EQUAL replica count, a prefill:1,decode:2 class
# tier (router-resident global prefix index + KV-block streaming between
# replicas) against today's symmetric 3-replica tier, on the mixed workload
# disaggregation exists for — warm-session probes (long shared prefix, one
# output token: the TTFT population), long-decode requests (the throughput
# carriers), and cold new sessions arriving mid-window (the interference).
# Every session prompt opens with the SAME 32-token system head (the chat
# regime), which is exactly the affinity map's blind spot: its key is the
# first `serve_affinity_tokens`=32 tokens, so every family collides on one
# key and overload spills re-learn the key elsewhere — each spill turns the
# next probe of EVERY family into a duplicate cold prefill.  The global
# index keys on whole-block prefixes longest-first, so families stay
# distinct and warm requests route to (or migrate to) the replica that
# already holds their blocks.
#
# One-core rig: like --replicas, real CPU decode serializes across replica
# processes, so each replica emulates a COMPUTE-BOUND device — every
# dispatch sleeps `wait * tokens_advanced` (prefill chunks cost their token
# count, prefix-hit admissions cost only the divergent tail, idle dispatches
# cost nothing).  Sleeps overlap across processes, so the tier topology —
# not the single host core — sets the wall time.  Device speed: not
# measured.

DISAGG_CLASSES = ("prefill", "decode", "decode")
DISAGG_BLOCK_TOKENS = 8
DISAGG_SHARED_HEAD = 32      # shared system head == default affinity_tokens
DISAGG_PREFIX_TOKENS = 64    # whole session prefix (8 full blocks)
DISAGG_FAMILIES = 4          # warm session families
DISAGG_HITS_PER_FAMILY = 10  # timed warm probes per family (TTFT samples)
DISAGG_DECODE_HEAVY = 12     # short-prompt long-decode requests
DISAGG_NEWCOMERS = 4         # cold sessions arriving inside the window
DISAGG_TOKEN_WAIT_S = 0.01   # emulated device seconds per token processed
DISAGG_OVERRIDES = {
    "sequence_length": 96, "serve_engine": "continuous", "kv_paging": "on",
    "kv_block_tokens": DISAGG_BLOCK_TOKENS, "kv_pool_blocks": 144,
    "serve_prefill_chunk_tokens": 8, "decode_chunk_tokens": 4,
    "trace_requests": True,
}


def _disagg_prefix(family: int):
    """Session prompt: the shared 32-token system head + a 32-token
    family-specific history (8 full blocks total)."""
    import numpy as np
    head = [((7 * i) % 251) + 1 for i in range(DISAGG_SHARED_HEAD)]
    rng = np.random.default_rng(5000 + family)
    tail = [int(x) for x in rng.integers(
        1, 255, DISAGG_PREFIX_TOKENS - DISAGG_SHARED_HEAD)]
    return head + tail


def _disagg_replica_main(cfg, port, index):
    """Replica subprocess body for the --disagg tiers: paged serving stack
    with the per-replica blackbox tag and the compute-bound device
    emulation (sleep per token each dispatch actually advanced)."""
    cfg = dict(cfg)
    wait = float(cfg.pop("_bench_tok_wait_s", 0.0) or 0.0)
    import numpy as np
    import jax.numpy as jnp
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.distributed.replica_fleet import install_replica_stop
    from homebrewnlp_tpu.infer.interface import InterfaceWrapper
    from homebrewnlp_tpu.infer.rest_api import serve
    from homebrewnlp_tpu.model import Model

    stop = install_replica_stop()
    params = ModelParameter(cfg)
    params.train = False
    if getattr(params, "trace_requests", False) and params.model_path:
        # replica-indexed blackbox tag BEFORE serve() (same discipline as
        # replica_fleet._replica_main) so forensics can merge the tier
        from homebrewnlp_tpu.telemetry import events as _flight
        _flight.configure(params.model_path, f"r{index}")
    if wait:
        from homebrewnlp_tpu.infer import paged as _paged
        _orig = _paged.PagedEngineExecutor.dispatch

        def _paced(self, steps, _orig=_orig):
            before = self.q.copy()
            out = _orig(self, steps)
            adv = float(np.clip(np.asarray(out) - before, 0, None).sum())
            if adv:
                time.sleep(wait * adv)
            return out

        _paged.PagedEngineExecutor.dispatch = _paced
    model = Model(params)
    seq = params.sequence_dim.size
    tps = params.token_patch_dim.size
    zeros = np.zeros((1, seq, tps), np.int32)
    variables = {k: jnp.asarray(v)
                 for k, v in model.init({"token_x": zeros,
                                         "token_y": zeros}).items()}
    interface = InterfaceWrapper(params, model, variables)
    print(f"[replica {index}] disagg bench replica "
          f"({cfg.get('serve_replica_class') or 'symmetric'}) on :{port}",
          flush=True)
    serve(params, interface, port=port, isolate=True, stop=stop)


def _load_forensics():
    """scripts/forensics.py as a module (the --trace merge helpers)."""
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "forensics.py")
    spec = importlib.util.spec_from_file_location("_bench_forensics", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _scrape_labeled(port, name):
    """{label_suffix: value} for one labeled series on /metrics."""
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
        text = resp.read().decode()
    out = {}
    for labels, val in re.findall(rf'^{name}{{([^}}]*)}} ([0-9.e+-]+)',
                                  text, re.M):
        out[labels] = out.get(labels, 0.0) + float(val)
    return out


def _disagg_timed_requests(args):
    """The seeded mixed workload: (kind, payload) list, shuffled."""
    import numpy as np
    reqs = []
    for f in range(DISAGG_FAMILIES):
        for j in range(DISAGG_HITS_PER_FAMILY):
            reqs.append(("probe", {"tokens": _disagg_prefix(f) + [30 + j],
                                   "max_tokens": 1, "temperature": 0.0}))
    # the held-back family (warmed cold-only, never re-probed in the warm
    # phase) migrates INSIDE the timed window, so the kv_transfer hop
    # rides a traced request into the merged per-hop rows
    for j in range(3):
        reqs.append(("probe", {"tokens": _disagg_prefix(DISAGG_FAMILIES)
                               + [70 + j],
                               "max_tokens": 1, "temperature": 0.0}))
    for i in range(DISAGG_DECODE_HEAVY):
        rng = np.random.default_rng(7000 + i)
        toks = [int(x) for x in rng.integers(1, 255, 4)]
        reqs.append(("decode", {"tokens": toks, "max_tokens": 32,
                                "temperature": 0.0}))
    for k in range(DISAGG_NEWCOMERS):
        reqs.append(("cold", {"tokens": _disagg_prefix(50 + k) + [9],
                              "max_tokens": 4, "temperature": 0.0}))
    order = np.random.default_rng(args.seed).permutation(len(reqs))
    return [reqs[i] for i in order]


def _run_disagg_tier(label: str, classes, args, wait_s: float) -> dict:
    """One tier (class topology or symmetric) end to end: real fleet +
    in-process router, warm/migrate phase, timed closed loop, merged
    per-hop trace rows."""
    import tempfile
    import numpy as np
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.distributed.replica_fleet import ReplicaFleet
    from homebrewnlp_tpu.infer import rest_api
    from homebrewnlp_tpu.infer.router import Replica, Router
    from homebrewnlp_tpu.telemetry import events as flight
    from homebrewnlp_tpu.telemetry import tracectx

    scratch = tempfile.mkdtemp(prefix=f"bench_disagg_{label}_")
    n = len(DISAGG_CLASSES)
    cfg = {**BENCH_CONFIG, **DISAGG_OVERRIDES, "serve_slots": args.slots,
           "model_path": scratch, "_bench_tok_wait_s": wait_s}
    params = ModelParameter({k: v for k, v in cfg.items()
                             if not k.startswith("_")})
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        router_port = s.getsockname()[1]
    base = router_port + 1
    fleet = ReplicaFleet(params, n, base_port=base,
                         target=_disagg_replica_main,
                         classes=list(classes) if classes else None)
    fleet.cfg = dict(cfg)  # ride the bench-only _bench_tok_wait_s through
    # the router IS this process: its blackbox (kv_transfer +
    # router/forward spans) lands next to the replicas' for the merge
    flight.recorder().clear()
    flight.configure(scratch, "router")
    router = Router([Replica(i, base + i) for i in range(n)],
                    forward_timeout_s=300.0, trace_requests=True,
                    classes=list(classes) if classes else None,
                    block_tokens=DISAGG_BLOCK_TOKENS,
                    kv_transfer_timeout_s=120.0)

    def dispatch(path, body, headers=None):
        if path == "/health":
            return router.health()
        if path == "/metrics":
            return {"_prometheus": router.metrics()}
        return router.forward(path, body, headers)

    def fire(payload, tid=None, timeout=600.0):
        headers = {tracectx.TRACE_HEADER: tid} if tid else None
        return _post(router_port, payload, timeout=timeout, headers=headers)

    def fire_all(payloads):
        threads = [threading.Thread(target=fire, args=(p,), daemon=True,
                                    name=f"bench-fire-{j}")
                   for j, p in enumerate(payloads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=600)

    canary_payload = {"tokens": _disagg_prefix(0) + [200], "max_tokens": 8,
                      "temperature": 0.0}
    results = []
    lock = threading.Lock()
    try:
        fleet.start()
        threading.Thread(
            target=rest_api._run_http, name="bench-disagg-router-http",
            args=(router_port,
                  ["/token_completion", "/health", "/metrics"],
                  dispatch, max(8, args.concurrency)), daemon=True).start()
        deadline = time.monotonic() + 900
        while True:
            try:
                h = _wait_up(router_port, deadline_s=30)
                if all("health" in r for r in h.get("replicas", ())):
                    break
            except Exception:
                pass
            if time.monotonic() > deadline:
                raise TimeoutError(f"{label} tier never came up")
            time.sleep(1.0)
        # compile warm, spread over the tier (in the class tier these are
        # short decodes -> the decode replicas; the prefill replica
        # compiles on the first session cold below)
        fire_all([{"tokens": [21 + i, 22, 23, 24], "max_tokens": 4,
                   "temperature": 0.0} for i in range(2 * n)])
        # session colds, sequential: exactly one cold prefill per family
        # (the +1 held-back family is warmed cold-only — its migration
        # happens inside the timed window, carrying a traced kv_transfer
        # span into the merged per-hop rows)
        for f in range(DISAGG_FAMILIES + 1):
            status, body = fire({"tokens": _disagg_prefix(f) + [9],
                                 "max_tokens": 1, "temperature": 0.0})
            assert status == 200, body
        # greedy canary, pass 1 (class tier: triggers family-0's
        # block migration to a decode replica)
        status, canary_a = fire(canary_payload)
        assert status == 200, canary_a
        # concurrent re-probes: the class tier migrates the remaining
        # families' blocks to decode replicas; the symmetric tier warms
        # its affinity map
        fire_all([{"tokens": _disagg_prefix(f) + [8], "max_tokens": 1,
                   "temperature": 0.0} for f in range(DISAGG_FAMILIES)])
        # greedy canary, pass 2 (class tier: answered by a decode-class
        # replica from the STREAMED blocks) — must match pass 1 bit-exact
        status, canary_b = fire(canary_payload)
        assert status == 200, canary_b

        shuffled = _disagg_timed_requests(args)
        workers = max(2, args.concurrency)

        def worker(w):
            for kind, payload in shuffled[w::workers]:
                tid = tracectx.new_trace_id()
                t_req = time.monotonic()
                try:
                    status, body = fire(payload, tid=tid)
                except Exception:
                    status, body = 599, {}
                wall = time.monotonic() - t_req
                gen = max(0, len(body.get("tokens", ()))
                          - len(payload["tokens"])) if status == 200 else 0
                with lock:
                    results.append((kind, wall, status, gen, tid))

        t0 = time.monotonic()
        threads = [threading.Thread(target=worker, args=(w,), daemon=True,
                                    name=f"bench-worker-{w}")
                   for w in range(workers)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.monotonic() - t0
        transfer = {
            "migrations": _scrape_labeled(router_port,
                                          "hbnlp_disagg_migrations_total"),
            "index": _scrape_labeled(router_port,
                                     "hbnlp_disagg_index_total"),
            "transfer_bytes": _scrape_values(
                router_port,
                ("hbnlp_disagg_transfer_bytes_total",))
            ["hbnlp_disagg_transfer_bytes_total"],
        }
        flight.flush(reason=f"bench-disagg-{label}")
    finally:
        fleet.stop()

    # merged per-hop rows (forensics --trace form): router blackbox
    # (router/forward + kv_transfer spans) + replica blackboxes + the
    # replicas' per-request trace exports, all under one scratch dir
    fz = _load_forensics()
    files = fz.load_files(fz.discover(scratch))
    per_hop, traced = {}, 0
    for kind, wall_r, status, gen, tid in results:
        rep = fz.trace_report(files, tid, scratch)
        hops = dict(rep["hops"])
        for k, v in ((rep.get("exported") or {}).get("hops") or {}).items():
            hops.setdefault(k, v)
        if hops:
            traced += 1
        for k, v in hops.items():
            per_hop.setdefault(k, []).append(v)
    hops_row = {"traced_requests": traced}
    for k, vals in sorted(per_hop.items()):
        hops_row[k] = {"p50": round(float(np.percentile(vals, 50)), 6),
                       "p99": round(float(np.percentile(vals, 99)), 6),
                       "n": len(vals)}

    errors = {}
    for kind, wall_r, status, gen, tid in results:
        if status != 200:
            errors[str(status)] = errors.get(str(status), 0) + 1
    ttfts = sorted(w for kind, w, status, gen, tid in results
                   if kind == "probe" and status == 200)
    gen_total = sum(gen for _, _, status, gen, _ in results if status == 200)
    return {
        "classes": ",".join(classes) if classes else "symmetric",
        "requests_ok": sum(1 for r in results if r[2] == 200),
        "errors": errors,
        "generated_tokens": gen_total,
        "wall_s": round(wall, 3),
        "tokens_per_sec": round(gen_total / max(wall, 1e-9), 2),
        "ttft_p50": round(float(np.percentile(ttfts, 50)), 4) if ttfts
        else None,
        "ttft_p99": round(float(np.percentile(ttfts, 99)), 4) if ttfts
        else None,
        "ttft_samples": len(ttfts),
        "canary": (canary_a.get("tokens"), canary_b.get("tokens")),
        "hops": hops_row,
        "transfer": transfer,
    }


def run_disagg(args) -> dict:
    sym = _run_disagg_tier("symmetric", None, args, DISAGG_TOKEN_WAIT_S)
    print(json.dumps({"disagg_symmetric_tier": sym}), flush=True)
    dis = _run_disagg_tier("classes", DISAGG_CLASSES, args,
                           DISAGG_TOKEN_WAIT_S)
    print(json.dumps({"disagg_class_tier": dis}), flush=True)
    canaries = [sym["canary"][0], sym["canary"][1],
                dis["canary"][0], dis["canary"][1]]
    parity = all(c == canaries[0] and c is not None for c in canaries)
    sym_row = {k: v for k, v in sym.items() if k != "canary"}
    dis_row = {k: v for k, v in dis.items() if k != "canary"}
    return {
        "mode": "disagg",
        "replicas": len(DISAGG_CLASSES),
        "device_token_wait_s": DISAGG_TOKEN_WAIT_S,
        "host_cores": os.cpu_count(),
        "note": ("compute-bound device emulation (sleep per token each "
                 "dispatch advanced) like the replicas row — the tier "
                 "topology, not the single host core, sets wall time; "
                 "every session prompt shares a 32-token system head, the "
                 "regime where the symmetric tier's affinity key "
                 "collides and overload spills duplicate cold prefills "
                 "while the global prefix index stays block-exact; "
                 "device speed not measured"),
        "workload": {
            "families": DISAGG_FAMILIES,
            "prefix_tokens": DISAGG_PREFIX_TOKENS,
            "shared_head_tokens": DISAGG_SHARED_HEAD,
            "hit_probes": DISAGG_FAMILIES * DISAGG_HITS_PER_FAMILY,
            "in_window_migration_probes": 3,
            "decode_heavy": DISAGG_DECODE_HEAVY,
            "cold_newcomers": DISAGG_NEWCOMERS,
        },
        "canary_parity": parity,
        "symmetric": sym_row,
        "disagg": dis_row,
        "tokens_per_sec_ratio": round(
            dis["tokens_per_sec"] / max(sym["tokens_per_sec"], 1e-9), 3),
        "ttft_p99_ratio": round(
            (dis["ttft_p99"] or 1e9) / max(sym["ttft_p99"] or 1e-9, 1e-9),
            3),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--engines", default="batch,continuous",
                    help="comma list: batch, continuous")
    ap.add_argument("--slots", type=int, default=4,
                    help="serve_slots for the continuous engine")
    ap.add_argument("--batch", type=int, default=4,
                    help="serve_batch_size for the batch engine (kept equal "
                         "to --slots by default for a fair width match)")
    ap.add_argument("--concurrency", type=int, default=8,
                    help="closed-loop worker count")
    ap.add_argument("--requests", type=int, default=6,
                    help="closed-loop requests per worker")
    ap.add_argument("--rate", type=float, default=4.0,
                    help="open-loop arrival rate (req/s)")
    ap.add_argument("--duration", type=float, default=8.0,
                    help="open-loop duration (s)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--config", default=None,
                    help="config JSON instead of the harness-scale model")
    ap.add_argument("--latency", default=None,
                    help="FaultyInterface schedule 'I:SEC[,I:SEC...]' — "
                         "decode call I sleeps SEC (batch-path decode calls)")
    ap.add_argument("--out", default="BENCH_SERVING.json")
    ap.add_argument("--spec", action="store_true",
                    help="speculative A/B: train the aligned target/draft "
                         "pair, run continuous vs spec on the permutation "
                         "workload, record acceptance (docs/SERVING.md)")
    ap.add_argument("--shared-prefix", action="store_true",
                    dest="shared_prefix",
                    help="paged-KV shared-prefix workload: common system "
                         "prompt + divergent tails; records prefix-hit vs "
                         "cold TTFT, greedy parity vs the plain engine, "
                         "and block occupancy (docs/SERVING.md 'Paged KV')")
    ap.add_argument("--spec-paged", action="store_true", dest="spec_paged",
                    help="composed spec-on-paged deployment "
                         "(spec_paged_chunk_step) vs the plain continuous "
                         "engine: closed-loop draft-and-verify speedup AND "
                         "prefix-hit vs cold TTFT in the SAME serving "
                         "process, at greedy bit-parity (docs/SERVING.md "
                         "'Engine architecture')")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode tier A/B: "
                         "prefill:1,decode:2 classes (KV-block streaming + "
                         "router global prefix index) vs the symmetric "
                         "3-replica tier at equal count, on a mixed "
                         "long-prefill/long-decode workload; records "
                         "aggregate tokens/sec, p99 TTFT, and merged "
                         "per-hop rows including the kv_transfer hop "
                         "(docs/SERVING.md 'Disaggregated tier')")
    ap.add_argument("--replicas", type=int, default=0,
                    help="multi-replica tier scaling sweep up to N "
                         "replicas behind the router (device-wait "
                         "emulation + real-model honesty rows; "
                         "docs/SERVING.md)")
    ap.add_argument("--spec-k", type=int, default=16, dest="spec_k",
                    help="spec_draft_tokens for the spec engine (verify "
                         "width k+1; tokens per round scale with it at "
                         "high acceptance — measured 1.5x at k=12, 2.0x "
                         "at k=16 on the CPU rig)")
    ap.add_argument("--trace", action="store_true",
                    help="enable request tracing on the served deployment "
                         "and record a p50/p99 per-hop breakdown "
                         "(queue-wait / prefill / decode / dispatch "
                         "overhead) of the closed-loop window into each "
                         "row's 'hops' key; the replicated tier's "
                         "router-dispatch hop merges via forensics.py "
                         "--trace (docs/OBSERVABILITY.md)")
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero unless continuous >= 1.5x batch "
                         "closed-loop tokens/sec AND lower p99 TTFT; with "
                         "--spec: spec >= 1.5x continuous at greedy "
                         "bit-parity (identical canary tokens); with "
                         "--spec-paged: composed >= 1.5x plain AND "
                         "prefix-hit TTFT <= 0.5x cold AND parity")
    args = ap.parse_args(argv)
    args.batch = args.batch or args.slots

    def merge_out(key, result):
        # these rows ride BENCH_SERVING.json NEXT TO the engine-comparison
        # row (the --spec convention) instead of overwriting it
        payload = {}
        if os.path.exists(args.out):
            try:
                with open(args.out) as f:
                    prior = json.load(f)
                payload = prior if isinstance(prior, dict) else {}
            except ValueError:
                payload = {}
        payload[key] = result
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)

    if args.shared_prefix:
        result = run_shared_prefix(args)
        merge_out("shared_prefix", result)
        print(json.dumps(result), flush=True)
        failures = []
        if args.check:
            if not result["canary_parity"]:
                failures.append("paged canary diverged from the plain "
                                "engine")
            if result["hit_over_cold"] > 0.5:
                failures.append(
                    f"prefix-hit TTFT {result['hit_ttft_median_s']}s is "
                    f"not << cold {result['cold_ttft_median_s']}s")
            occ = result["occupancy"]
            if not (0 < occ["peak_blocks_in_use"]
                    < occ["slot_engine_equivalent_blocks"]):
                failures.append("block occupancy does not track live "
                                f"tokens: {occ}")
            if result["prefix_hit_tokens"] <= 0:
                failures.append("no prefix hits recorded")
        if failures:
            print("CHECK FAILED: " + "; ".join(failures), flush=True)
            return 1
        return 0

    if args.spec_paged:
        result = run_spec_paged(args)
        merge_out("spec_paged", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k not in ("cold_ttft_s", "hit_ttft_s")}),
              flush=True)
        failures = []
        if args.check:
            if not result["canary_parity"]:
                failures.append("composed canary diverged from the plain "
                                "continuous engine")
            if result["tokens_per_sec_speedup"] < 1.5:
                failures.append(
                    f"composed speedup {result['tokens_per_sec_speedup']} "
                    "< 1.5x plain continuous")
            if result["hit_over_cold"] > 0.5:
                failures.append(
                    f"prefix-hit TTFT {result['hit_ttft_median_s']}s is "
                    f"not <= 0.5x cold {result['cold_ttft_median_s']}s")
            if result["prefix_hit_tokens"] <= 0:
                failures.append("no prefix hits recorded")
            if result["spec"]["drafted"] <= 0:
                failures.append("no draft tokens recorded")
        if failures:
            print("CHECK FAILED: " + "; ".join(failures), flush=True)
            return 1
        return 0

    if args.disagg:
        result = run_disagg(args)
        merge_out("disagg", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k != "note"}), flush=True)
        failures = []
        if args.check:
            if not result["canary_parity"]:
                failures.append("disagg canary diverged (streamed-block "
                                "answers must be bit-identical to the "
                                "symmetric tier's)")
            if result["tokens_per_sec_ratio"] <= 1.0:
                failures.append(
                    f"disagg tokens/sec ratio "
                    f"{result['tokens_per_sec_ratio']} <= 1.0x symmetric")
            if result["ttft_p99_ratio"] >= 1.0:
                failures.append(
                    f"disagg p99 TTFT ratio {result['ttft_p99_ratio']} "
                    ">= 1.0x symmetric")
            kv_hop = result["disagg"]["hops"].get("kv_transfer") or {}
            if not kv_hop.get("n"):
                failures.append("no kv_transfer hop spans in the merged "
                                "disagg trace")
            if result["disagg"]["errors"] or result["symmetric"]["errors"]:
                failures.append(
                    f"request errors: disagg={result['disagg']['errors']} "
                    f"symmetric={result['symmetric']['errors']}")
            if not result["disagg"]["transfer"]["migrations"].get(
                    'outcome="ok"'):
                failures.append("no successful block migrations recorded")
        if failures:
            print("CHECK FAILED: " + "; ".join(failures), flush=True)
            return 1
        return 0

    if args.replicas >= 2:
        result = run_replicas(args)
        merge_out("replicas", result)
        print(json.dumps({k: v for k, v in result.items()
                          if k != "note"}), flush=True)
        if args.check:
            worst = min(r["scaling_efficiency"] for r in result["curve"])
            if worst < 0.7:
                print(f"CHECK FAILED: emulated replica scaling efficiency "
                      f"{worst} < 0.7", flush=True)
                return 1
        return 0

    latency = None
    if args.latency:
        latency = {int(k): float(v) for k, v in
                   (kv.split(":") for kv in args.latency.split(","))}

    spec_ctx = None
    if args.spec:
        if args.engines == "batch,continuous":
            args.engines = "continuous,spec"
        interface, draft, align = _build_spec_pair()
        print(json.dumps({"spec_alignment": align}), flush=True)
        spec_ctx = {"interface": interface, "draft": draft,
                    "orbit": _spec_perm(), "alignment": align}

    rows = []
    for engine in args.engines.split(","):
        engine = engine.strip()
        row = run_engine(engine, args, latency=latency, spec_ctx=spec_ctx)
        rows.append(row)
        print(json.dumps(row), flush=True)

    result = {
        "metric": "serving tokens/sec + TTFT/ITL @ mixed-length REST "
                  "traffic (closed+open loop)",
        "workload": list(WORKLOAD if spec_ctx is None else SPEC_WORKLOAD),
        "slots": args.slots, "batch": args.batch,
        "concurrency": args.concurrency, "rate_rps": args.rate,
        "backend": "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu"
        else "default",
        "rows": rows,
    }
    if spec_ctx is not None:
        result["spec_alignment"] = spec_ctx["alignment"]
    by = {r["engine"]: r for r in rows}
    if "batch" in by and "continuous" in by:
        b = by["batch"]["closed_loop"]["tokens_per_sec"]
        c = by["continuous"]["closed_loop"]["tokens_per_sec"]
        result["tokens_per_sec_speedup"] = round(c / max(b, 1e-9), 3)
        bt, ct = by["batch"].get("ttft_p99"), by["continuous"].get("ttft_p99")
        result["ttft_p99_batch"] = bt
        result["ttft_p99_continuous"] = ct
    if "continuous" in by and "spec" in by:
        c = by["continuous"]["closed_loop"]["tokens_per_sec"]
        s = by["spec"]["closed_loop"]["tokens_per_sec"]
        result["spec_tokens_per_sec_speedup"] = round(s / max(c, 1e-9), 3)
        result["spec_canary_parity"] = (
            by["spec"]["canary"] is not None
            and by["spec"]["canary"] == by["continuous"]["canary"])
    payload = {}
    if os.path.exists(args.out):
        try:
            with open(args.out) as f:
                prior = json.load(f)
            payload = prior if isinstance(prior, dict) else {}
        except ValueError:
            payload = {}
    if args.spec:
        # the spec round rides BENCH_SERVING.json NEXT TO the PR 7
        # continuous-vs-batch row instead of overwriting it
        payload["spec"] = result
    else:
        # the headline row is the top level; re-measuring it must not
        # drop the nested spec/shared_prefix/replicas rows other modes
        # merged in earlier
        extra = {k: payload[k] for k in ("spec", "shared_prefix",
                                         "spec_paged", "replicas")
                 if k in payload}
        payload = {**result, **extra}
    with open(args.out, "w") as f:
        json.dump(payload, f, indent=1)
    print(json.dumps({k: v for k, v in result.items() if k != "rows"}),
          flush=True)
    failures = []
    if args.check and "tokens_per_sec_speedup" in result:
        bt, ct = result["ttft_p99_batch"], result["ttft_p99_continuous"]
        # an absent quantile means the timed window recorded no TTFT
        # samples — no latency evidence either way, so the gate FAILS
        # loudly instead of passing vacuously
        if not (result["tokens_per_sec_speedup"] >= 1.5
                and bt is not None and ct is not None and ct <= bt):
            failures.append("continuous-vs-batch gate")
    if args.check and "spec_tokens_per_sec_speedup" in result:
        if result["spec_tokens_per_sec_speedup"] < 1.5:
            failures.append(
                f"spec speedup {result['spec_tokens_per_sec_speedup']} "
                "< 1.5x")
        if not result.get("spec_canary_parity"):
            failures.append("spec canary diverged from the plain engine")
    if args.check and args.spec \
            and "spec_tokens_per_sec_speedup" not in result:
        failures.append("--spec --check needs both continuous and spec rows")
    if failures:
        print("CHECK FAILED: " + "; ".join(failures), flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
