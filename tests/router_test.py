"""Multi-replica serving tier (marker: router; docs/SERVING.md).

Device-free sweep: the router dispatch policy on fake transports — prefix
affinity stickiness + the overload override, least-loaded fallback,
per-replica breaker open/skip/probe/reclose with a fake clock, the
one-cross-replica-retry rule, 503-when-all-open, and the /metrics
relabel-merge.  Plus the replica fleet supervisor on stub process targets
(relaunch with backoff, budget exhaustion raises).

Device sweep (one test): the real tier end to end — two replica
subprocesses of a tiny paged-engine model behind the router — answering
completions deterministically, merging /health, and exporting
replica-labeled block-pool gauges on one scrape.

Standalone-runnable:
``python -m pytest tests/router_test.py -q``
"""
import json
import os
import sys
import threading
import time
import urllib.request

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from homebrewnlp_tpu.infer.router import (Replica, Router,
                                          relabel_exposition)
from homebrewnlp_tpu.infer.serving_guard import HTTPStatusError

pytestmark = pytest.mark.router


def _router(n=3, t=None, transport=None, **kw):
    t = t if t is not None else [0.0]
    reps = [Replica(i, 9000 + i, breaker_threshold=2, breaker_cooldown_s=5.0,
                    clock=lambda: t[0]) for i in range(n)]
    kw.setdefault("affinity_tokens", 4)
    r = Router(reps, transport=transport or (lambda *a: (200, {"ok": True})),
               clock=lambda: t[0], **kw)
    return r, reps, t


# ------------------------------------------------------------ dispatch policy

def affinity_sticks_and_yields_to_load_test():
    """Same prompt prefix -> same replica; a different prefix goes least-
    loaded; an overloaded sticky replica is overridden."""
    router, reps, _ = _router()
    body = {"tokens": [1, 2, 3, 4, 9, 9], "max_tokens": 4}
    first = router.pick("/token_completion", body)
    reps[(first.index + 1) % 3].inflight = 0
    first.inflight = 2                      # busier, but within slack
    again = router.pick("/token_completion",
                        {"tokens": [1, 2, 3, 4, 7], "max_tokens": 2})
    assert again is first                    # prefix (first 4 tokens) sticks
    # beyond the slack the router yields to least-loaded
    first.inflight = 10
    moved = router.pick("/token_completion",
                        {"tokens": [1, 2, 3, 4, 8], "max_tokens": 2})
    assert moved is not first
    # a cold prefix dispatches least-loaded
    reps[2].inflight = 0
    reps[0].inflight = reps[1].inflight = 5
    cold = router.pick("/token_completion",
                       {"tokens": [42, 42, 42, 42], "max_tokens": 2})
    assert cold is reps[2]


def breaker_skip_retry_and_reclose_test():
    """Failures open a replica's breaker (dispatch skips it), a forward
    retries ONCE on another replica, all-open answers 503 + Retry-After,
    and the half-open probe recloses after the cooldown."""
    calls = []

    def transport(replica, path, body, timeout):
        calls.append(replica.index)
        if replica.index == 0:
            return 500, {"error": "boom", "code": "server_error"}
        return 200, {"ok": replica.index}

    router, reps, t = _router(n=2, transport=transport)
    reps[0].inflight = 0
    reps[1].inflight = 1                    # replica 0 preferred
    out = router.forward("/encode", {"prompt": "x"})
    assert out == {"ok": 1} and calls == [0, 1]   # failed, retried on 1
    out = router.forward("/encode", {"prompt": "x"})
    assert calls == [0, 1, 0, 1]
    assert reps[0].breaker.state == "open"  # threshold 2 reached
    calls.clear()
    out = router.forward("/encode", {"prompt": "x"})
    assert calls == [1]                     # open replica skipped entirely
    # all open -> 503 with Retry-After, no transport call
    reps[1].breaker.state = "open"
    reps[1].breaker.open_until = t[0] + 3.0
    calls.clear()
    with pytest.raises(HTTPStatusError) as exc:
        router.forward("/encode", {"prompt": "x"})
    assert exc.value.status == 503 and calls == []
    assert exc.value.retry_after >= 1.0
    # cooldown elapses: half-open admits the probe; replica 1's success
    # recloses it
    t[0] = 10.0
    out = router.forward("/encode", {"prompt": "x"})
    assert out == {"ok": 1}
    assert reps[1].breaker.state == "closed"


def unreachable_replica_counts_and_retries_test():
    """Connection-level failures convert to 502, count into the breaker,
    and retry on a healthy replica; client errors (4xx) do NOT count as
    replica failures."""
    def transport(replica, path, body, timeout):
        if replica.index == 0:
            raise ConnectionRefusedError("down")
        if body.get("bad"):
            return 400, {"error": "bad prompt", "code": "bad_request"}
        return 200, {"ok": replica.index}

    router, reps, _ = _router(n=2, transport=transport)
    reps[1].inflight = 5                    # replica 0 preferred
    assert router.forward("/encode", {}) == {"ok": 1}
    assert reps[0].failures == 1
    # a 400 answers the client untouched and leaves the breaker closed
    reps[0].breaker.state = "open"          # force traffic to replica 1
    reps[0].breaker.open_until = 100.0
    with pytest.raises(HTTPStatusError) as exc:
        router.forward("/encode", {"bad": True})
    assert exc.value.status == 400
    assert reps[1].breaker.state == "closed" and reps[1].failures == 0


def relabel_exposition_test():
    """Sample lines gain replica="<i>" (label-set-aware), HELP/TYPE lines
    dedupe across replicas, malformed lines drop."""
    text = ("# HELP hbnlp_x total\n# TYPE hbnlp_x counter\n"
            "hbnlp_x 3\n"
            'hbnlp_y{path="/completion"} 1.5\n'
            "garbage line without value-number-structure{{{\n")
    seen = set()
    out0 = relabel_exposition(text, 0, seen)
    out1 = relabel_exposition(text, 1, seen)
    assert 'hbnlp_x{replica="0"} 3' in out0
    assert 'hbnlp_y{replica="0",path="/completion"} 1.5' in out0
    assert "# HELP hbnlp_x total" in out0
    # second replica: samples relabeled, meta deduped
    assert 'hbnlp_x{replica="1"} 3' in out1
    assert not any(line.startswith("#") for line in out1)
    assert not any("garbage" in line for line in out0 + out1)


def router_health_merge_test():
    """/health aggregates per-replica state and stays "ok" while any
    replica is dispatchable; every breaker open -> "unavailable"."""
    router, reps, t = _router(n=2)
    payload = router.health(probe=lambda r: json.dumps({"status": "ok"}))
    assert payload["status"] == "ok"
    assert [e["replica"] for e in payload["replicas"]] == [0, 1]
    assert all(e["health"] == {"status": "ok"}
               for e in payload["replicas"])
    # unreachable probe is recorded per replica, not fatal
    def flaky(r):
        if r.index == 0:
            raise ConnectionRefusedError("down")
        return json.dumps({"status": "ok"})
    payload = router.health(probe=flaky)
    assert payload["status"] == "ok"
    assert "unreachable" in payload["replicas"][0]
    assert payload["tier"]["reachable"] == 1
    # NOTHING reachable = unavailable even with closed breakers: replicas
    # still loading their model must not read as a routable tier
    def down(r):
        raise ConnectionRefusedError("starting up")
    payload = router.health(probe=down)
    assert payload["status"] == "unavailable"
    ok, ready = router.ready(probe=down)
    assert not ok and ready["replicas_ready"] == 0
    ok, ready = router.ready(probe=lambda r: "{}" if r.index == 1
                             else (_ for _ in ()).throw(OSError("down")))
    assert ok and ready["replicas_ready"] == 1
    for r in reps:
        r.breaker.state = "open"
        r.breaker.open_until = t[0] + 10
    payload = router.health(probe=flaky)
    assert payload["status"] == "unavailable"


# ---------------------------------------------------- disagg owner failover

def kill_the_owner_degrades_to_cold_prefill_test():
    """Disaggregated tier, owner death mid-traffic: when the global prefix
    index names an owner that is GONE (connection refused) or breaker-open,
    the request falls back to cold prefill on another replica, the stale
    index entries are invalidated, and the client gets EXACTLY one answer
    — never a 500, never a duplicate."""
    from homebrewnlp_tpu.infer.router import KV_BLOCKS_PATH

    answered = []                            # successful completion answers
    dead = set()

    def transport(replica, path, body, timeout, headers=None):
        if replica.index in dead:
            raise ConnectionRefusedError(f"replica {replica.index} killed")
        if path == KV_BLOCKS_PATH:
            if body.get("op") == "export":
                toks = body["tokens"]
                return 200, {"version": 1, "block_tokens": 4,
                             "blocks": [{"key": toks[i:i + 4],
                                         "leaves": {"t/k": {"bytes": 8}}}
                                        for i in range(0, len(toks), 4)]}
            return 200, {"injected": 1, "skipped": 0}
        answered.append(replica.index)
        return 200, {"tokens": [9], "replica": replica.index}

    t = [0.0]
    reps = [Replica(i, 9000 + i, breaker_threshold=2, breaker_cooldown_s=5.0,
                    clock=lambda: t[0]) for i in range(3)]
    router = Router(reps, transport=transport, clock=lambda: t[0],
                    classes=["prefill", "decode", "decode"], block_tokens=4)
    toks = list(range(1, 10))                # 2 whole blocks + 1
    # warm: cold run lands on the prefill replica, migration hands the
    # blocks (and ownership) to a decode replica
    router.forward("/token_completion", {"tokens": toks})
    out = router.forward("/token_completion", {"tokens": toks})
    owner = out["replica"]
    assert reps[owner].cls == "decode"
    assert router.gindex.lookup(toks)[0] == owner
    # KILL the owner: the very next request must still answer, exactly once
    dead.add(owner)
    answered.clear()
    out = router.forward("/token_completion", {"tokens": toks})
    assert out["replica"] != owner
    assert answered == [out["replica"]]      # exactly-one-answer invariant
    # stale entries dropped and ownership re-learned on the survivor
    assert router.gindex.lookup(toks)[0] == out["replica"]
    assert all(v != owner for v in router.gindex._map.values())
    # breaker-open owner (not yet dead at the transport level) also
    # degrades without a transport call reaching it
    victim = out["replica"]
    for _ in range(2):
        reps[victim].breaker.record_failure()
    assert reps[victim].breaker.tick() == "open"
    answered.clear()
    out = router.forward("/token_completion", {"tokens": toks})
    assert out["replica"] != victim and answered == [out["replica"]]
    assert all(v != victim for v in router.gindex._map.values())


def symmetric_tier_never_consults_kv_blocks_test():
    """Classless (or single-class) replica lists leave the global index
    off: forward() is byte-identical to the pre-disagg router."""
    from homebrewnlp_tpu.infer.router import KV_BLOCKS_PATH
    paths = []

    def transport(replica, path, body, timeout, headers=None):
        paths.append(path)
        return 200, {"ok": replica.index}

    router, _, _ = _router(transport=transport)
    assert router.gindex is None
    router.forward("/token_completion", {"tokens": list(range(12))})
    assert KV_BLOCKS_PATH not in paths


# ------------------------------------------------------------ fleet stubs

def _stub_replica_ok(cfg, port, index):
    time.sleep(600)


def _stub_replica_dies(cfg, port, index):
    sys.exit(3)


def replica_fleet_relaunch_and_budget_test():
    """Dead replicas relaunch with backoff; the budget bounds crash LOOPS
    and raises when exhausted (a fleet silently shrinking to zero is worse
    than a loud failure)."""
    from homebrewnlp_tpu.distributed.replica_fleet import ReplicaFleet

    class _P:
        _raw_config = {"model_path": "/tmp/fleet_test"}
        serve_child_max_restarts = 1
        serve_child_restart_backoff_s = 0.05

    fleet = ReplicaFleet(_P(), 2, base_port=0, target=_stub_replica_ok)
    try:
        fleet.start()
        deadline = time.monotonic() + 30
        while fleet.alive() < 2 and time.monotonic() < deadline:
            time.sleep(0.1)
        assert fleet.alive() == 2
        # kill one replica: poll relaunches it within the backoff window
        fleet._procs[0].terminate()
        fleet._procs[0].join(timeout=10)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            fleet.poll()
            if (fleet._procs[0] is not None and fleet._procs[0].is_alive()
                    and fleet._restarts[0] == 1):
                break
            time.sleep(0.05)
        assert fleet.alive() == 2 and fleet._restarts[0] == 1
    finally:
        fleet.stop()
    # a replica that keeps dying exhausts its budget loudly
    fleet = ReplicaFleet(_P(), 1, base_port=0, target=_stub_replica_dies)
    try:
        fleet.start()
        with pytest.raises(RuntimeError, match="relaunches were exhausted"):
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                fleet.poll()
                time.sleep(0.05)
    finally:
        fleet.stop()


# ------------------------------------------------------------- end to end

def replica_tier_end_to_end_test():
    """Two real replica subprocesses (tiny paged-engine model) behind the
    router: deterministic completions through the tier, merged /health,
    and ONE /metrics scrape carrying replica-labeled engine + block-pool
    series next to the router's own dispatch counters."""
    import socket
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.infer.router import serve_replicated

    cfg = {
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
        "decode_loop": "stepped", "decode_chunk_tokens": 4,
        "serve_engine": "continuous", "serve_slots": 2,
        "kv_paging": "on", "kv_block_tokens": 4, "serve_replicas": 2,
        "model_path": "/tmp/router_tier_test",
    }
    params = ModelParameter(cfg)
    params.train = False
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    stop = threading.Event()
    t = threading.Thread(target=serve_replicated, args=(params,),
                         kwargs=dict(port=port, stop=stop), daemon=True)
    t.start()

    def req(path, payload=None, timeout=120):
        if payload is None:
            r = urllib.request.Request(f"http://127.0.0.1:{port}{path}")
        else:
            r = urllib.request.Request(
                f"http://127.0.0.1:{port}{path}",
                data=json.dumps(payload).encode(),
                headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(r, timeout=timeout) as resp:
            return resp.status, resp.read().decode()

    try:
        deadline = time.monotonic() + 420
        while True:
            try:
                _, body = req("/health")
                h = json.loads(body)
                if all("health" in r for r in h["replicas"]):
                    break
            except Exception:
                pass
            assert time.monotonic() < deadline, "tier never came up"
            time.sleep(1.0)
        assert h["status"] == "ok" and h["tier"]["replicas"] == 2
        payload = {"tokens": [1, 2, 3], "max_tokens": 4, "temperature": 0.0}
        st, body = req("/token_completion", payload)
        assert st == 200
        first = json.loads(body)["tokens"]
        # replicas share init seed and greedy decode: answers are
        # deterministic whichever replica serves the retry
        st, body = req("/token_completion", payload)
        assert st == 200 and json.loads(body)["tokens"] == first
        st, text = req("/metrics")
        assert st == 200
        assert 'replica="0"' in text and 'replica="1"' in text
        assert "hbnlp_router_requests_total" in text
        assert "hbnlp_kv_blocks_total" in text
        assert "hbnlp_serve_slots_total" in text
    finally:
        stop.set()
        t.join(timeout=60)
