"""Flight recorder + cross-process request tracing (marker ``forensics``;
docs/OBSERVABILITY.md 'Flight recorder' / 'Request tracing', ISSUE 15).

Three tiers:

- **Unit sweep** (device-free): the bounded event ring + blackbox dump
  discipline, size-capped jsonl rotation, trace-context header/coverage/
  hop math, the forensics causal merge (KV-observed orderings beating a
  skewed wall clock), the straggler detector state machine on a fake KV,
  and breaker-trip events.
- **Tracing e2e** (slow, real model): a single continuous-engine
  deployment served twice — tracing off vs on — proving greedy output
  stays BYTE-IDENTICAL, plus a real 2-replica tier where one client
  request's trace id lands in the router's, the replica HTTP child's, and
  the engine device loop's event files, with the merged per-request spans
  covering >= 95% of measured client wall time.
- **Forensics e2e** (slow): SIGKILL one rank of a 4-process elastic fleet
  (the tests/elastic_test.py worker); ``scripts/forensics.py`` over the
  surviving blackboxes reconstructs the incident — names the killed rank,
  orders the survivors' lease-lapse observations, shows the membership
  exits — with every survivor's ring flushed through the exit-144
  force-exit path.  A second fleet test artificially delays one rank and
  asserts the chief's straggler detector flags it BEFORE any lease lapse.
"""
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(HERE, "..", "scripts"))

import forensics  # noqa: E402  (scripts/forensics.py — jax-free)
from homebrewnlp_tpu.telemetry import events as flight  # noqa: E402
from homebrewnlp_tpu.telemetry import tracectx  # noqa: E402
from homebrewnlp_tpu.telemetry.events import (FlightRecorder,  # noqa: E402
                                              RotatingJsonl)

pytestmark = pytest.mark.forensics

WORKER = os.path.join(HERE, "_elastic_train_worker.py")


@pytest.fixture
def fresh_recorder():
    prev = flight.set_recorder()
    yield flight.recorder()
    flight.set_recorder(prev)


# ------------------------------------------------------------------ ring/dump

def flight_recorder_ring_test(tmp_path):
    """Bounded ring, monotone seq, dump format, throttled re-flush."""
    clock = [10.0]
    rec = FlightRecorder(capacity=4, clock=lambda: clock[0],
                         wall=lambda: clock[0] + 1000)
    for i in range(7):
        rec.record("step", step=i)
    evs = rec.events()
    assert len(evs) == 4 and [e["step"] for e in evs] == [3, 4, 5, 6]
    assert [e["seq"] for e in evs] == [4, 5, 6, 7]  # seq survives eviction
    assert rec.flush() is None                      # unconfigured: no dump
    rec.configure(str(tmp_path), "p3")
    path = rec.flush(reason="test")
    lines = [json.loads(x) for x in open(path)]
    assert lines[0]["blackbox"]["tag"] == "p3"
    assert [x["kind"] for x in lines[1:]] == ["step"] * 4
    assert all(x["proc"] == "p3" for x in lines[1:])
    # throttle: clean ring -> no dump; dirty + interval elapsed -> dump
    assert rec.maybe_flush(0.0) is None
    rec.record("exit", code=0)
    assert rec.maybe_flush(60.0) is None            # within the interval
    clock[0] += 61.0
    assert rec.maybe_flush(60.0) == path
    # capacity 0 = dump disabled (ring keeps recording in-memory)
    off = FlightRecorder()
    off.configure(str(tmp_path), "poff", capacity=0)
    off.record("x")
    assert off.flush() is None and len(off.events()) == 1
    # non-JSON field values degrade to str instead of failing the dump
    rec.record("odd", obj=object())
    assert isinstance(rec.events("odd")[0]["obj"], str)


def rotating_jsonl_test(tmp_path):
    """telemetry.jsonl growth satellite: past the cap the file rotates to
    .1/.2 keeping N generations, each opening with the header line."""
    path = str(tmp_path / "telemetry.jsonl")
    w = RotatingJsonl(path, max_mb=0.0001, keep=2, header='{"build": 1}')
    for i in range(120):
        w.write(json.dumps({"i": i, "pad": "x" * 40}))
    w.close()
    assert os.path.exists(path + ".1") and os.path.exists(path + ".2")
    assert not os.path.exists(path + ".3")          # beyond keep: deleted
    for p in (path, path + ".1", path + ".2"):
        assert json.loads(open(p).readline()) == {"build": 1}
    # an operator SHRINKING keep across a restart: orphans from the old
    # setting are reclaimed on the next rotation, not leaked forever
    for i in (3, 4, 5):
        open(f"{path}.{i}", "w").write("orphan\n")
    w2 = RotatingJsonl(path, max_mb=0.0001, keep=2, header='{"build": 1}')
    for i in range(120):
        w2.write(json.dumps({"i": i, "pad": "x" * 40}))
    w2.close()
    assert not any(os.path.exists(f"{path}.{i}") for i in (3, 4, 5))
    # cap 0 = unbounded, no rotation artifacts
    p2 = str(tmp_path / "unbounded.jsonl")
    w2 = RotatingJsonl(p2, max_mb=0.0, keep=2, header='{"build": 2}')
    for i in range(50):
        w2.write(json.dumps({"i": i}))
    w2.close()
    assert not os.path.exists(p2 + ".1")


def tracectx_unit_test(tmp_path):
    """Header extraction (case-insensitive, length-capped), span math:
    hop totals, interval-union coverage, chrome export."""
    assert tracectx.trace_id_from_headers(
        {"X-HBNLP-Trace-Id": "abc123"}) == "abc123"
    assert tracectx.trace_id_from_headers(
        {"x-hbnlp-trace-id": "abc123"}) == "abc123"
    assert tracectx.trace_id_from_headers({}) is None
    assert tracectx.trace_id_from_headers(None) is None
    assert tracectx.trace_id_from_headers(
        {"x-hbnlp-trace-id": "z" * 99}) is None     # hostile length
    # a client id becomes a server-side filename: path characters are
    # malformed, the edge mints a fresh id instead
    for evil in ("a/../b", "a.b", "a b", "..", "a\\b"):
        assert tracectx.trace_id_from_headers(
            {"x-hbnlp-trace-id": evil}) is None, evil
    a, b = tracectx.new_trace_id(), tracectx.new_trace_id()
    assert a != b and len(a) == 32
    t = tracectx.RequestTrace("tid1", rid="r1")
    t.add("queue_wait", 0.0, 1.0)
    t.add("chunk/prefill", 1.0, 0.25)
    t.add("chunk/decode", 1.25, 0.5)
    t.add("chunk/decode", 1.75, 0.25)
    assert t.hops() == {"queue_wait": 1.0, "prefill": 0.25, "decode": 0.75}
    assert abs(tracectx.coverage(t.spans, 0.0, 2.0) - 1.0) < 1e-9
    assert abs(tracectx.coverage(t.spans, 0.0, 4.0) - 0.5) < 1e-9
    # overlapping spans must not double-count
    t.add("request", 0.0, 2.0)
    assert abs(tracectx.coverage(t.spans, 0.0, 4.0) - 0.5) < 1e-9
    path = t.dump(str(tmp_path / "traces"))
    payload = json.load(open(path))
    assert payload["trace_id"] == "tid1" and payload["rid"] == "r1"
    assert payload["hops"]["decode"] == 0.75
    assert all(ev["ph"] == "X" for ev in payload["traceEvents"])


def record_span_cross_process_form_test(fresh_recorder):
    """record_span lands kind=span events with the trace id — the form
    forensics --trace merges; a None id is a no-op."""
    tracectx.record_span(None, "x", 0.0, 1.0)
    assert fresh_recorder.events() == []
    tracectx.record_span("tid", "router/forward", 5.0, 0.5, replica=1)
    ev = fresh_recorder.events("span")[0]
    assert ev["trace"] == "tid" and ev["name"] == "router/forward"
    assert ev["t0"] == 5.0 and ev["dur"] == 0.5 and ev["replica"] == 1


def breaker_trip_records_event_test(fresh_recorder):
    """Breaker transitions are flight-recorder events (tentpole: breaker
    trips in the blackbox), recorded at trip/reclose only."""
    from homebrewnlp_tpu.infer.serving_guard import CircuitBreaker
    t = [0.0]
    b = CircuitBreaker(2, 5.0, clock=lambda: t[0])
    b.record_failure()
    assert fresh_recorder.events("breaker") == []   # below threshold
    b.record_failure()
    trips = fresh_recorder.events("breaker")
    assert len(trips) == 1 and trips[0]["state"] == "open"
    t[0] = 6.0
    assert b.tick() == "half_open"
    b.record_success()
    states = [e["state"] for e in fresh_recorder.events("breaker")]
    assert states == ["open", "closed"]


# ------------------------------------------------------------- causal merge

def _write_blackbox(d, tag, events):
    with open(os.path.join(d, f"blackbox_{tag}.jsonl"), "w") as f:
        f.write(json.dumps({"blackbox": {"tag": tag}}) + "\n")
        for e in events:
            f.write(json.dumps(dict(e, proc=tag)) + "\n")


def causal_merge_beats_wall_clock_test(tmp_path):
    """The merge's whole point: p2's wall clock runs ~60s BEHIND p1's, so
    a sort-by-wall would place p2's lease scan BEFORE the p1 beat it
    observed — the KV-observed (beat -> scan) edge must win, with wall
    time only breaking the remaining ties."""
    d = str(tmp_path)
    _write_blackbox(d, "p1", [
        {"kind": "beat", "rank": 1, "beat": 1, "seq": 1, "wall": 100.0},
        {"kind": "beat", "rank": 1, "beat": 2, "seq": 2, "wall": 101.0},
    ])
    _write_blackbox(d, "p2", [
        {"kind": "lease_scan", "rank": 2, "peers": {"1": 2}, "seq": 1,
         "wall": 40.0},                              # skewed 60s early
        {"kind": "exit", "rank": 2, "code": 0, "seq": 2, "wall": 41.0},
    ])
    files = forensics.load_files(forensics.discover(d))
    order = forensics.causal_order(files)
    idx = {(e["proc"], e.get("beat"), e["kind"]): i
           for i, e in enumerate(order)}
    assert idx[("p2", None, "lease_scan")] > idx[("p1", 2, "beat")]
    assert idx[("p2", None, "exit")] > idx[("p2", None, "lease_scan")]


def forensics_analyze_names_killed_rank_test(tmp_path):
    """Incident reconstruction on synthetic blackboxes: the rank peers
    declared lapsed with no exit record of its own is the first-failing
    rank; survivors' lapse observations come out in causal order and
    their 144 force-exits are listed."""
    d = str(tmp_path)
    _write_blackbox(d, "p1", [
        {"kind": "beat", "rank": 1, "beat": 5, "seq": 1, "wall": 50.0},
    ])
    _write_blackbox(d, "p0", [
        {"kind": "lease_scan", "rank": 0, "peers": {"1": 5}, "seq": 1,
         "wall": 100.0},
        {"kind": "membership", "rank": 0, "lapsed": [1], "seq": 2,
         "cause": "peer lease(s) lapsed: p1", "wall": 108.0},
        {"kind": "exit", "rank": 0, "code": 144, "path": "force",
         "seq": 3, "wall": 108.1},
    ])
    _write_blackbox(d, "p2", [
        {"kind": "lease_scan", "rank": 2, "peers": {"1": 5}, "seq": 1,
         "wall": 39.0},
        {"kind": "membership", "rank": 2, "lapsed": [1], "seq": 2,
         "cause": "peer lease(s) lapsed: p1", "wall": 47.0},
        {"kind": "exit", "rank": 2, "code": 144, "path": "force",
         "seq": 3, "wall": 47.1},
    ])
    report = forensics.analyze(forensics.load_files(forensics.discover(d)))
    assert report["first_failing_rank"] == 1
    assert report["killed_ranks"] == [1]
    # a STALE prior-generation ring must not exonerate the victim: p1's
    # gen-0 file ends in a clean exit, but the gen-1 incident still names
    # it (events are generation-filtered to the newest membership gen)
    d2 = str(tmp_path / "gen_stale")
    os.makedirs(d2)
    _write_blackbox(d2, "p1", [
        {"kind": "beat", "rank": 1, "beat": 9, "gen": 0, "seq": 1,
         "wall": 10.0},
        {"kind": "exit", "rank": 1, "code": 144, "gen": 0, "path": "force",
         "seq": 2, "wall": 11.0},
    ])
    _write_blackbox(d2, "p0", [
        {"kind": "membership", "rank": 0, "lapsed": [1], "gen": 1,
         "cause": "peer lease(s) lapsed: p1", "seq": 1, "wall": 60.0},
        {"kind": "exit", "rank": 0, "code": 144, "gen": 1, "path": "force",
         "seq": 2, "wall": 60.1},
    ])
    stale = forensics.analyze(forensics.load_files(forensics.discover(d2)))
    assert stale["first_failing_rank"] == 1, stale["killed_ranks"]
    assert [o["observer"] for o in report["lapse_observations"]] \
        == ["p2", "p0"]                              # causal order
    assert {e["proc"] for e in report["membership_exits"]} == {"p0", "p2"}
    text = forensics.format_report(report)
    assert "FIRST-FAILING RANK: p1" in text
    # the CLI agrees
    out = subprocess.run([sys.executable,
                          os.path.join(HERE, "..", "scripts",
                                       "forensics.py"), d, "--json"],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout)["first_failing_rank"] == 1


def forensics_trace_mode_test(tmp_path):
    """--trace merges one request's spans across process files into the
    per-hop view."""
    d = str(tmp_path)
    _write_blackbox(d, "router", [
        {"kind": "span", "trace": "t1", "name": "router/forward",
         "t0": 1.0, "dur": 0.9, "seq": 1, "wall": 10.0},
    ])
    _write_blackbox(d, "r0", [
        {"kind": "span", "trace": "t1", "name": "queue_wait",
         "t0": 1.1, "dur": 0.2, "seq": 1, "wall": 10.1},
        {"kind": "span", "trace": "t1", "name": "chunk/decode",
         "t0": 1.3, "dur": 0.5, "seq": 2, "wall": 10.3},
        {"kind": "span", "trace": "OTHER", "name": "chunk/decode",
         "t0": 9.0, "dur": 0.5, "seq": 3, "wall": 11.0},
    ])
    files = forensics.load_files(forensics.discover(d))
    rep = forensics.trace_report(files, "t1")
    assert len(rep["spans"]) == 3
    assert rep["hops"] == {"router/forward": 0.9, "queue_wait": 0.2,
                           "decode": 0.5}
    out = subprocess.run([sys.executable,
                          os.path.join(HERE, "..", "scripts",
                                       "forensics.py"), d,
                          "--trace", "t1"],
                         capture_output=True, text=True)
    assert out.returncode == 0 and "router/forward" in out.stdout


# ------------------------------------------------------- straggler detector

class _FakeKV:
    def __init__(self):
        self.store = {}

    def put(self, key, value):
        self.store[key] = value
        return True

    def dir_get(self, prefix):
        return [(k, v) for k, v in self.store.items()
                if k.startswith(prefix)]

    def beat(self, pid, seq, step=None, gen=0):
        d = {"seq": seq, "ospid": 1000 + pid}
        if step is not None:
            d["step"] = step
        self.store[f"hbnlp/elastic/g{gen}/p{pid}"] = json.dumps(d)


def straggler_detector_test(tmp_path):
    """The chief flags a slow-but-alive rank — lease beating, published
    step lagging the fleet — BEFORE its lease lapses; ranks AT the fleet
    max (finished / sync-blocked fast ranks) are exempt, and an advance
    re-arms the flag."""
    from homebrewnlp_tpu.distributed.elastic import ElasticAgent

    clock, steps, flags = [0.0], [0], []
    kv = _FakeKV()
    rec = FlightRecorder(clock=lambda: clock[0], wall=lambda: clock[0])
    agent = ElasticAgent(
        str(tmp_path), 0, 3, gen=0, interval_s=0.5, timeout_s=60.0,
        exit_grace_s=0.1, kv_put=kv.put, kv_dir_get=kv.dir_get,
        clock=lambda: clock[0], exit_fn=lambda rc: None,
        progress=lambda: steps[0], straggler_factor=4.0,
        on_straggler=lambda r, age, med: flags.append(r), recorder=rec)
    agent._started_at = 0.0
    for t in range(1, 12):
        clock[0] = t * 0.5
        steps[0] = t                                  # chief advances
        kv.beat(1, t, step=t)                         # p1 advances
        kv.beat(2, t, step=min(t, 2))                 # p2 stalls at step 2
        agent.tick()
    assert agent.event is None                        # no lapse: alive
    assert flags == [2], flags                        # flagged exactly once
    ev = rec.events("straggler")[0]
    assert ev["rank"] == 2 and ev["step"] == 2 \
        and ev["fleet_max"] > ev["step"] and ev["stall_s"] > 0
    # recovery re-arms: p2 advances, stalls again -> a second flag
    for t in range(12, 24):
        clock[0] = t * 0.5
        steps[0] = t
        kv.beat(1, t, step=t)
        kv.beat(2, t, step=min(t, 14))                # advances, re-stalls
        agent.tick()
    assert flags == [2, 2], flags
    # the beat/scan causality anchors rode along
    assert len(rec.events("beat")) == 23
    assert rec.events("lease_scan")[-1]["peers"]["1"] == 23


def membership_force_exit_flushes_blackbox_test(tmp_path):
    """The exit-144 force-exit path (os._exit skips every finally) must
    leave the incident on disk: membership detection flushes immediately,
    and _trigger_exit records exit path=force + flushes after the
    pre-exit hook."""
    from homebrewnlp_tpu.distributed.elastic import (ElasticAgent,
                                                     MEMBERSHIP_EXIT_CODE)

    calls = []
    rec = FlightRecorder()
    rec.configure(str(tmp_path), "p0")
    agent = ElasticAgent(
        str(tmp_path), 0, 2, gen=0, exit_grace_s=0.0,
        kv_put=lambda k, v: True, kv_dir_get=lambda p: [],
        exit_fn=lambda rc: calls.append(rc),
        pre_exit=lambda: calls.append("pre"), recorder=rec)
    agent._record_event("peer lease(s) lapsed: p1", lapsed=[1])
    agent._trigger_exit()
    assert calls == ["pre", MEMBERSHIP_EXIT_CODE]
    lines = [json.loads(x) for x in
             open(os.path.join(str(tmp_path), "blackbox_p0.jsonl"))]
    kinds = [x.get("kind") for x in lines[1:]]
    assert kinds == ["membership", "exit"]
    assert lines[-1]["code"] == MEMBERSHIP_EXIT_CODE
    assert lines[-1]["path"] == "force"


# ---------------------------------------------------------- metric-docs rule
# (the positive half — repo-at-HEAD clean — rides static_analysis_test's
# existing head-clean sweep; these are the rule's own negative controls)

def metric_docs_rule_test(tmp_path):
    from homebrewnlp_tpu.analysis import ast_lint

    src_dir = tmp_path / "homebrewnlp_tpu"
    os.makedirs(src_dir)
    (src_dir / "m.py").write_text(
        "r.counter('hbnlp_fake_metric_total', 'x')\n"
        "r.gauge('hbnlp_documented_gauge', 'y')\n"
        "r.histogram('hbnlp_suppressed_seconds', "
        "'z')  # graft-lint: allow[metric-docs]\n"
        "r.counter(SOME_NAME, 'variables are out of scope')\n")
    md = tmp_path / "OBS.md"
    md.write_text("| `hbnlp_documented_gauge` | gauge | ... |\n")
    found = ast_lint.metric_docs_findings(
        root=str(tmp_path), subdirs=("homebrewnlp_tpu",),
        obs_md=str(md))
    assert len(found) == 1 and "hbnlp_fake_metric_total" in found[0].message
    assert found[0].rule == "metric-docs"
    # adding the row clears it
    md.write_text("| `hbnlp_documented_gauge` | ... |\n"
                  "| `hbnlp_fake_metric_total` | ... |\n")
    assert ast_lint.metric_docs_findings(
        root=str(tmp_path), subdirs=("homebrewnlp_tpu",),
        obs_md=str(md)) == []


# --------------------------------------------------------------- tracing e2e

_TIER_CFG = {
    "model_mode": "gpt", "use_video": False, "use_language": True,
    "sequence_length": 16, "features_per_head": 8, "heads": 2,
    "depth": 1, "train_batch_size": 1, "vocab_size": 64,
    "group_linear_factor": 2,
    "intermediate_feed_forward_multiplier_multiplier": 0.5,
    "memory_reduction_strategy": "none",
    "block_config": [
        {"layer": ["norm-shift-scale-features-group",
                   "attention-biased_attention_map-absolute-"
                   "input_as_value-shared"]}],
    "decode_loop": "stepped", "decode_chunk_tokens": 2,
    "serve_engine": "continuous", "serve_slots": 2,
}


def _serve_single(cfg, port):
    """One in-process continuous-engine deployment (isolate=True: real
    Manager + HTTP child), stoppable."""
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.infer import rest_api
    from homebrewnlp_tpu.infer.interface import InterfaceWrapper
    from homebrewnlp_tpu.model import Model
    import jax.numpy as jnp

    params = ModelParameter(cfg)
    params.train = False
    model = Model(params)
    seq, tps = params.sequence_dim.size, params.token_patch_dim.size
    zeros = np.zeros((1, seq, tps), np.int32)
    variables = {k: jnp.asarray(v) for k, v in
                 model.init({"token_x": zeros, "token_y": zeros}).items()}
    interface = InterfaceWrapper(params, model, variables)
    stop = threading.Event()
    t = threading.Thread(target=rest_api.serve, args=(params, interface),
                         kwargs=dict(port=port, isolate=True, stop=stop),
                         daemon=True)
    t.start()
    return stop, t


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port, path, payload, headers=None, timeout=180):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


def _wait_up(port, deadline_s=420):
    t0 = time.monotonic()
    while True:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/health", timeout=10) as resp:
                return json.loads(resp.read())
        except Exception:
            assert time.monotonic() - t0 < deadline_s, "server never came up"
            time.sleep(0.5)


@pytest.mark.slow
def tracing_parity_and_export_test(tmp_path, fresh_recorder):
    """Acceptance: with tracing enabled, served greedy output stays
    BYTE-IDENTICAL (the tracer only observes), and the per-request
    Chrome-trace export lands with queue-wait + chunk spans."""
    payload = {"tokens": [3, 1, 4, 1, 5], "max_tokens": 6,
               "temperature": 0.0}
    outs = {}
    for mode, trace_on in (("off", False), ("on", True)):
        cfg = dict(_TIER_CFG, trace_requests=trace_on,
                   model_path=str(tmp_path / mode))
        os.makedirs(cfg["model_path"], exist_ok=True)
        port = _free_port()
        stop, t = _serve_single(cfg, port)
        try:
            _wait_up(port)
            _post(port, "/token_completion", payload)   # warmup compile
            tid = tracectx.new_trace_id()
            st, body = _post(port, "/token_completion", payload,
                             headers={tracectx.TRACE_HEADER: tid})
            assert st == 200
            outs[mode] = (body["tokens"], tid, cfg["model_path"])
        finally:
            stop.set()
            t.join(timeout=60)
    assert outs["on"][0] == outs["off"][0], \
        "tracing must not change served greedy output"
    # the traced request exported its per-request chrome JSON with the
    # client's OWN id (header adoption at the HTTP edge)
    _, tid, mp = outs["on"]
    trace_path = os.path.join(mp, "traces", f"trace_{tid}.json")
    assert os.path.exists(trace_path), os.listdir(mp)
    payload_json = json.load(open(trace_path))
    names = {s["name"] for s in payload_json["spans"]}
    assert "queue_wait" in names and "request" in names
    assert any(n.startswith("chunk/") for n in names)
    assert payload_json["hops"].get("decode", 0) > 0
    # the untraced deployment exported nothing
    assert not os.path.exists(os.path.join(outs["off"][2], "traces"))
    # device-loop + HTTP-child blackboxes landed (flushed on stop/SIGTERM)
    assert os.path.exists(os.path.join(mp, "blackbox_serve.jsonl"))


@pytest.mark.slow
def trace_propagation_replica_tier_test(tmp_path, fresh_recorder):
    """The headline tracing e2e: through a REAL 2-replica tier, one trace
    id appears in the router's, a replica HTTP child's, and the engine
    device loop's event files, and the merged per-request spans cover
    >= 95% of measured client wall time."""
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.infer.router import serve_replicated

    model_path = str(tmp_path / "tier")
    os.makedirs(model_path)
    cfg = dict(_TIER_CFG, serve_replicas=2, trace_requests=True,
               model_path=model_path)
    params = ModelParameter(cfg)
    params.train = False
    port = _free_port()
    stop = threading.Event()
    t = threading.Thread(target=serve_replicated, args=(params,),
                         kwargs=dict(port=port, stop=stop), daemon=True)
    t.start()
    try:
        deadline = time.monotonic() + 420
        while True:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/health",
                        timeout=10) as resp:
                    h = json.loads(resp.read())
                if all("health" in r for r in h["replicas"]):
                    break
            except Exception:
                pass
            assert time.monotonic() < deadline, "tier never came up"
            time.sleep(1.0)
        payload = {"tokens": [1, 2, 3], "max_tokens": 8,
                   "temperature": 0.0}
        _post(port, "/token_completion", payload)       # warmup compiles
        _post(port, "/token_completion", payload)
        tid = tracectx.new_trace_id()
        t0 = time.monotonic()
        st, body = _post(port, "/token_completion", payload,
                         headers={tracectx.TRACE_HEADER: tid})
        t1 = time.monotonic()
        assert st == 200 and body["tokens"]
    finally:
        stop.set()
        t.join(timeout=120)
    files = forensics.load_files(forensics.discover(model_path))
    tags = set(files)
    assert "router" in tags, tags
    assert any(re.fullmatch(r"r\d+_http", tag) for tag in tags), tags
    assert any(re.fullmatch(r"r\d+", tag) for tag in tags), tags
    # ONE trace id, three processes' event files
    with_trace = {tag for tag, evs in files.items()
                  if any(e.get("trace") == tid for e in evs)}
    assert "router" in with_trace, with_trace
    assert any(re.fullmatch(r"r\d+_http", tag) for tag in with_trace), \
        with_trace
    assert any(re.fullmatch(r"r\d+", tag) for tag in with_trace), with_trace
    # the merged per-request trace covers >= 95% of client wall time
    spans = []
    for evs in files.values():
        spans.extend(tracectx.spans_from_events(evs, tid))
    assert spans
    cov = tracectx.coverage(spans, t0, t1)
    assert cov >= 0.95, (cov, sorted((s["proc"], s["name"]) for s in spans))
    # forensics --trace reconstructs the hop chain
    rep = forensics.trace_report(files, tid, model_path=model_path)
    assert rep["hops"].get("router/forward", 0) > 0
    assert rep["hops"].get("decode", 0) > 0
    assert rep["exported"] is not None              # the replica's export


# -------------------------------------------------------------- forensics e2e

def _fleet_cfg(tmp_path, data_dir, **over):
    cfg = {
        "model_mode": "gpt", "use_video": False, "use_language": True,
        "sequence_length": 32, "features_per_head": 8, "heads": 2,
        "depth": 1, "train_batch_size": 12, "vocab_size": 32,
        "tpu_size": 4, "calc_accuracy": False,
        "block_config": [{"layer": ["norm-shift-scale-features-group",
                                    "feed_forward-in:relu"]}],
        "memory_reduction_strategy": "none",
        "optimizer": "adam-learning_rate", "learning_rate": 1e-3,
        "weight_decay": 0.0, "mesh_shape_override": {"data": 4},
        "train_steps": 200, "use_checkpointing": True,
        "steps_per_checkpoint": 8, "checkpoint_async": True,
        "max_checkpoints_keep": 50, "interleaved_datasets": 2,
        "data_seed": 7, "storage_retry_base_delay": 0.0,
        "distributed_barrier_timeout_s": 30.0,
        "elastic_training": True, "elastic_lease_interval_s": 0.5,
        "elastic_lease_timeout_s": 5.0, "elastic_exit_grace_s": 0.0,
        "dataset_configs": [{"path": str(data_dir / "*"), "type": "text",
                             "weight": 1}],
        "model_path": str(tmp_path / "run"),
    }
    cfg.update(over)
    return cfg


def _spawn_fleet(cfg_path, n, extra=()):
    port = _free_port()
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS=flags + " --xla_force_host_platform_device_count=1")
    return [subprocess.Popen(
        [sys.executable, WORKER, str(port), str(pid), str(n),
         str(cfg_path), *[str(a) for a in extra]],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True) for pid in range(n)]


@pytest.mark.slow
def forensics_fleet_sigkill_e2e_test(tmp_path):
    """The headline forensics acceptance: SIGKILL one rank of a 4-process
    elastic fleet; every survivor's ring flushes through the exit-144
    FORCE-exit path (exit_grace 0 -> the agent's os._exit, never the
    finally), and scripts/forensics.py over the surviving blackboxes
    reconstructs the incident: names the killed rank, orders the
    lease-lapse observations across survivors, and shows the membership
    exits.

    The kill is timed into a provably-quiet window: the step delay
    exceeds the lease timeout, and the kill fires only after the lease
    mirror shows EVERY rank past the step-4 sync point (the log-cadence
    float sync drains all pending collectives) — every survivor then
    host-sleeps with idle gloo sockets, so the lease scans (beating on
    the agent daemon thread) detect the lapse and force-exit BEFORE any
    collective touches the dead rank's closed sockets — the
    clean-144-everywhere shape.  On the 1-core CI box, scheduler
    starvation can delay detection past the sleep window, in which case a
    survivor's next collective hits the closed sockets and gloo SIGABRTs
    it ('another task died') — the documented contention flake every
    fleet test retries once on (multihost_test._spawn_workers policy);
    this test does the same with a fresh run dir.  (The controller-level
    handling of that messier collateral shape is tests/elastic_test.py's
    e2e.)"""
    from elastic_test import _write_records

    last = None
    for attempt in range(2):
        run_dir = tmp_path / f"attempt{attempt}"
        os.makedirs(run_dir)
        data_dir = run_dir / "data"
        _write_records(data_dir, 12, 4096)
        cfg = _fleet_cfg(run_dir, data_dir)
        model_path = cfg["model_path"]
        cfg_path = run_dir / "cfg.json"
        cfg_path.write_text(json.dumps(cfg))

        procs = _spawn_fleet(cfg_path, 4, extra=("--step-delay", "15.0"))
        victim_pidfile = os.path.join(model_path, "pids", "g0_p1.pid")
        leases = os.path.join(model_path, "elastic", "leases.json")

        def _fleet_past_sync() -> bool:
            """Every rank's mirrored step-ENTRY >= 5: all hosts passed
            the step-4 float sync (which drains every pending collective)
            and are sleeping inside their attempt of step 5."""
            try:
                mirror = json.load(open(leases))
            except (OSError, json.JSONDecodeError):
                return False
            entries = mirror.get("leases", {})
            return len(entries) == 4 and all(
                e.get("step", 0) >= 5 for e in entries.values())

        killed = False
        deadline = time.monotonic() + 420
        try:
            while time.monotonic() < deadline:
                if not killed and os.path.exists(victim_pidfile) \
                        and _fleet_past_sync():
                    time.sleep(1.0)  # everyone ~1s into a 15s host sleep
                    os.kill(int(open(victim_pidfile).read()),
                            signal.SIGKILL)
                    killed = True
                if all(p.poll() is not None for p in procs):
                    break
                time.sleep(0.25)
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
        outs = [p.communicate(timeout=30)[0] for p in procs]
        assert killed
        rcs = [p.returncode for p in procs]
        assert rcs[1] == -signal.SIGKILL, (rcs, outs[1][-1500:])
        survivors = [i for i in range(4) if i != 1]
        last = (rcs, outs, model_path, survivors)
        if all(rcs[i] == 144 for i in survivors):
            break
        if attempt == 0:
            # Same classified guard as multihost_test._spawn_workers: this
            # site needs its own spawn loop (mid-flight SIGKILL timing), so
            # it shares the classifier rather than the spawner — the reason
            # stamped here is the same line every fleet retry logs.
            from multihost_test import starvation_retry_reason
            reason = starvation_retry_reason(
                [rcs[i] for i in survivors], [outs[i] for i in survivors])
            if reason:
                print(f"FLEET RETRY: {reason} — gloo SIGABRT before the "
                      "lease scan fired; retrying with a fresh run dir",
                      flush=True)
                continue
        break
    rcs, outs, model_path, survivors = last
    assert all(rcs[i] == 144 for i in survivors), \
        (rcs, "\n".join(o[-1200:] for o in outs))
    # every survivor's blackbox flushed through the force-exit path
    files = forensics.load_files(forensics.discover(model_path))
    for i in survivors:
        evs = files.get(f"p{i}")
        assert evs, sorted(files)
        exits = [e for e in evs if e["kind"] == "exit"]
        assert exits and exits[-1]["code"] == 144, exits
        assert exits[-1]["path"] == "force", exits
        assert any(e["kind"] == "membership" and 1 in e["lapsed"]
                   for e in evs), f"p{i} recorded no membership event"
    # the merged reconstruction names the killed rank and the exits
    report = forensics.analyze(files)
    assert report["first_failing_rank"] == 1, report["killed_ranks"]
    observers = [o["observer"] for o in report["lapse_observations"]]
    assert len(observers) >= 2 \
        and set(observers) <= {"p0", "p2", "p3"}, observers
    assert {e["proc"] for e in report["membership_exits"]} \
        == {f"p{i}" for i in survivors}
    out = subprocess.run([sys.executable,
                          os.path.join(HERE, "..", "scripts",
                                       "forensics.py"), model_path],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "FIRST-FAILING RANK: p1" in out.stdout, out.stdout[-2000:]


@pytest.mark.slow
def straggler_flagged_in_fleet_test(tmp_path):
    """Acceptance: the straggler detector flags an artificially-delayed
    rank in a REAL fleet before its lease lapses — the run completes
    cleanly (no membership exit), with the flag in the chief's output and
    blackbox.

    The delayed rank WEDGES once for ~15s (GC-pause / storage-stall
    shape) rather than running proportionally slower: synchronous
    training equalizes fleet-average step rates (collectives gate
    everyone), so a same-order slowdown is invisible by construction —
    the detectable straggler is the one whose step stalls for many
    fleet-median step intervals while its lease keeps beating."""
    from elastic_test import _write_records
    from multihost_test import _spawn_workers

    data_dir = tmp_path / "data"
    _write_records(data_dir, 12, 4096)
    cfg = _fleet_cfg(
        tmp_path, data_dir, tpu_size=3, train_batch_size=12,
        mesh_shape_override={"data": 3}, train_steps=8,
        use_checkpointing=False, checkpoint_async=False,
        elastic_lease_interval_s=0.25, elastic_lease_timeout_s=120.0,
        elastic_straggler_factor=3.0)
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))
    results = _spawn_workers(
        WORKER, [str(cfg_path), "--straggle-rank", "2",
                 "--straggle-delay", "15.0", "--straggle-step", "3"],
        env_devcount=1, n_procs=3, timeout=420)
    assert all(p.returncode == 0 for p, _ in results), \
        "\n".join(o[-1500:] for _, o in results)
    chief_out = results[0][1]
    assert "ELASTIC: straggler suspected p2" in chief_out, chief_out[-2500:]
    assert "membership change" not in chief_out
    # the flag landed in the chief's blackbox too — before any lease
    # event (there was none: every rank finished rc 0)
    evs = forensics.load_files(
        [os.path.join(cfg["model_path"], "blackbox_p0.jsonl")])["p0"]
    st = [e for e in evs if e["kind"] == "straggler"]
    assert st and st[0]["rank"] == 2, [e["kind"] for e in evs]
    assert not [e for e in evs if e["kind"] == "membership"]
