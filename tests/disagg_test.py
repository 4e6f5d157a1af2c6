"""Disaggregated prefill/decode serving (marker: disagg; docs/SERVING.md
'Disaggregated tier').

Device-free sweep: the wire-format discipline (bf16 + int8-scale leaves
round-trip bit-exactly, crc corruption and geometry mismatches rejected
loudly with zero side effects), the router-resident global prefix index,
the class-topology parser, and the router's class-aware dispatch state
machine (miss -> prefill owner, hit -> route-to-owner or migrate, owner
death -> cold fallback) driven with fake transports.

Device sweep: greedy bit-parity of a decode-class executor consuming
STREAMED blocks against the same prompt prefilled locally — the streamed
admission takes the ordinary prefix-hit path (prefill skipped over the
injected span) — plus the two-replica REST round trip over the real
``/kv/blocks`` seam.

Standalone-runnable:
``python -m pytest tests/disagg_test.py -q``
"""
import base64
import json
import socket
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from backend import MIXER_BLOCKS, make_params
from homebrewnlp_tpu.infer import kv_transfer
from homebrewnlp_tpu.infer.router import (GlobalPrefixIndex, KV_BLOCKS_PATH,
                                          Replica, Router,
                                          parse_replica_classes)
from homebrewnlp_tpu.infer.scheduler import (EngineController, EngineRequest,
                                             SlotScheduler)
from homebrewnlp_tpu.infer.serving_guard import HTTPStatusError

pytestmark = pytest.mark.disagg


# ------------------------------------------------------------ device harness

def _interface(**kw):
    from homebrewnlp_tpu.infer.interface import InterfaceWrapper
    from homebrewnlp_tpu.model import Model
    import jax.numpy as jnp
    cfg = dict(block_config=MIXER_BLOCKS, memory_reduction_strategy="none",
               sequence_length=32, train_batch_size=1,
               decode_loop="stepped", decode_chunk_tokens=5)
    cfg.update(kw)
    params = make_params(**cfg)
    params.train = False
    model = Model(params)
    seq = params.sequence_dim.size
    batch = {"token_x": np.zeros((1, seq, 1), np.int32),
             "token_y": np.zeros((1, seq, 1), np.int32)}
    variables = {k: jnp.asarray(v) for k, v in model.init(batch).items()}
    return InterfaceWrapper(params, model, variables)


def _paged_controller(iface, slots=4, block_tokens=4, pool_blocks=None):
    from homebrewnlp_tpu.infer.paged import PagedEngineExecutor
    ex = PagedEngineExecutor(iface, slots=slots, block_tokens=block_tokens,
                             pool_blocks=pool_blocks)
    answers = {}
    sched = SlotScheduler(ex.slots, clock=time.monotonic)
    ctl = EngineController(
        ex, sched, clock=time.monotonic, decode_chunk=5, prefill_chunk=8,
        answer=lambda req, oc: answers.__setitem__(req.rid, oc))
    return ex, ctl, answers


def _serve(ctl, answers, reqs, rounds=80):
    ctl.round(reqs)
    for _ in range(rounds):
        if all(r.rid in answers for r in reqs):
            return
        ctl.round()
    raise AssertionError(f"unanswered: "
                         f"{[r.rid for r in reqs if r.rid not in answers]}")


def _req(rid, toks, rl):
    return EngineRequest(rid=rid, path="/token_completion",
                         toks=np.asarray(toks, np.int32), response_len=rl)


# -------------------------------------------------------------- wire format

def wire_roundtrip_bf16_test():
    """Export a served prompt's cached blocks, inject them into a FRESH
    executor, re-export: every leaf's bytes survive bit-exactly, and the
    destination tree holds the same root chain."""
    iface = _interface()
    ex_a, ctl_a, ans_a = _paged_controller(iface)
    prompt = list(range(1, 17)) + [21, 22]   # 16 shared tokens = 4 blocks
    _serve(ctl_a, ans_a, [_req("p", prompt, 4)])
    payload = kv_transfer.export_blocks(ex_a, prompt)
    assert len(payload["blocks"]) == 4
    assert payload["block_tokens"] == 4
    assert kv_transfer.payload_bytes(payload) > 0
    for blk in payload["blocks"]:
        for meta in blk["leaves"].values():
            assert meta["crc_algo"] in ("crc32", "crc32c-masked")
    ex_b, _, _ = _paged_controller(iface)
    res = kv_transfer.inject_blocks(ex_b, json.loads(json.dumps(payload)))
    assert res == {"injected": 4, "skipped": 0, "blocks": 4}
    back = kv_transfer.export_blocks(ex_b, prompt)
    assert [b["key"] for b in back["blocks"]] \
        == [b["key"] for b in payload["blocks"]]
    for sent, got in zip(payload["blocks"], back["blocks"]):
        assert set(sent["leaves"]) == set(got["leaves"])
        for name in sent["leaves"]:
            assert sent["leaves"][name]["data"] \
                == got["leaves"][name]["data"], name
    # re-injecting the same payload: existing children win, nothing moves
    again = kv_transfer.inject_blocks(ex_b, payload)
    assert again == {"injected": 0, "skipped": 4, "blocks": 4}


def wire_roundtrip_int8_scale_leaves_test():
    """int8 KV deployments stream BOTH the int8 rows and their f32 scale
    siblings; the round trip is bit-exact for both."""
    iface = _interface(decode_cache_dtype="int8")
    ex_a, ctl_a, ans_a = _paged_controller(iface)
    prompt = list(range(1, 14)) + [40]
    _serve(ctl_a, ans_a, [_req("p", prompt, 4)])
    payload = kv_transfer.export_blocks(ex_a, prompt)
    assert payload["blocks"]
    dtypes = {name: meta["dtype"]
              for name, meta in payload["blocks"][0]["leaves"].items()}
    assert any(n.endswith("_scale") for n in dtypes), dtypes
    assert "int8" in set(dtypes.values()), dtypes
    for name, dt in dtypes.items():
        if name.endswith("_scale"):
            assert dt == "float32", (name, dt)
    ex_b, _, _ = _paged_controller(iface)
    res = kv_transfer.inject_blocks(ex_b, payload)
    assert res["injected"] == len(payload["blocks"])
    back = kv_transfer.export_blocks(ex_b, prompt)
    for sent, got in zip(payload["blocks"], back["blocks"]):
        for name in sent["leaves"]:
            assert sent["leaves"][name]["data"] \
                == got["leaves"][name]["data"], name


def corrupt_payload_rejected_loudly_test():
    """A flipped byte, a bad version, mismatched geometry, and a wrong
    leaf set must each raise ValueError BEFORE any pool mutation."""
    iface = _interface()
    ex_a, ctl_a, ans_a = _paged_controller(iface)
    prompt = list(range(1, 17))
    _serve(ctl_a, ans_a, [_req("p", prompt, 3)])
    payload = kv_transfer.export_blocks(ex_a, prompt)
    assert payload["blocks"]

    def fresh():
        ex, _, _ = _paged_controller(iface)
        return ex

    # crc corruption: flip one byte of one leaf, keep the recorded crc
    bad = json.loads(json.dumps(payload))
    name = sorted(bad["blocks"][0]["leaves"])[0]
    meta = bad["blocks"][0]["leaves"][name]
    raw = bytearray(base64.b64decode(meta["data"]))
    raw[0] ^= 0xFF
    meta["data"] = base64.b64encode(bytes(raw)).decode("ascii")
    ex = fresh()
    with pytest.raises(ValueError, match="verification|truncated"):
        kv_transfer.inject_blocks(ex, bad)
    assert len(ex.tree) == 0                 # zero side effects
    # truncation is caught by the length check even without the crc
    bad = json.loads(json.dumps(payload))
    meta = bad["blocks"][0]["leaves"][name]
    meta["data"] = base64.b64encode(
        base64.b64decode(meta["data"])[:-2]).decode("ascii")
    with pytest.raises(ValueError, match="truncated"):
        kv_transfer.inject_blocks(fresh(), bad)
    # wire-version and geometry refusals
    with pytest.raises(ValueError, match="version"):
        kv_transfer.inject_blocks(fresh(), dict(payload, version=99))
    with pytest.raises(ValueError, match="block_tokens"):
        kv_transfer.inject_blocks(fresh(), dict(payload, block_tokens=8))
    # a leaf set from some other deployment
    bad = json.loads(json.dumps(payload))
    bad["blocks"][0]["leaves"]["target/not_a_leaf"] = \
        dict(bad["blocks"][0]["leaves"][name])
    with pytest.raises(ValueError, match="leaves"):
        kv_transfer.inject_blocks(fresh(), bad)


def streamed_blocks_greedy_bit_parity_test():
    """The decode-side contract: after injection, admitting the SAME
    prompt takes the prefix-hit path (prefill skipped over the streamed
    span) and the greedy output is bit-identical to a cold local
    prefill."""
    iface = _interface()
    ex_a, ctl_a, ans_a = _paged_controller(iface)
    prompt = list(range(1, 17)) + [25]       # 4 full blocks + 1
    _serve(ctl_a, ans_a, [_req("p", prompt, 6)])
    payload = kv_transfer.export_blocks(ex_a, prompt)
    assert len(payload["blocks"]) == 4

    ex_b, ctl_b, ans_b = _paged_controller(iface)
    res = kv_transfer.inject_blocks(ex_b, payload)
    assert res["injected"] == 4
    st0 = dict(ex_b.pool_stats())
    assert st0["blocks_cached"] >= 4
    _serve(ctl_b, ans_b, [_req("q", prompt, 6)])
    st1 = ex_b.pool_stats()
    assert st1["prefix_hits"] == st0["prefix_hits"] + 1
    assert st1["prefix_hit_tokens"] - st0["prefix_hit_tokens"] == 16
    kind, got = ans_b["q"]
    assert kind == "ok"
    want = np.asarray(iface.complete_tokens(np.asarray(prompt, np.int32),
                                            0.0, 6))
    np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(np.asarray(ans_a["p"][1]), want)


def index_digest_reports_tree_paths_test():
    iface = _interface()
    ex, ctl, answers = _paged_controller(iface)
    assert kv_transfer.index_digest(ex)["paths"] == []
    prompt = list(range(1, 17))
    _serve(ctl, answers, [_req("p", prompt, 3)])
    digest = kv_transfer.index_digest(ex)
    assert digest["block_tokens"] == 4
    assert prompt in digest["paths"]
    capped = kv_transfer.index_digest(ex, max_paths=0)
    assert capped["paths"] == []


# --------------------------------------------------------- global index unit

def global_prefix_index_test():
    g = GlobalPrefixIndex(block_tokens=4, cap=8)
    g.record(list(range(12)), owner=2)       # 3 whole-block prefixes
    assert len(g) == 3
    owner, depth = g.lookup(list(range(14)))  # longer prompt, same prefix
    assert owner == 2 and depth == 12
    owner, depth = g.lookup(list(range(6)))   # shorter: 1-block prefix
    assert owner == 2 and depth == 4
    assert g.lookup([9, 9, 9, 9]) == (None, 0)
    assert g.lookup([0, 1]) == (None, 0)      # sub-block span never matches
    assert g.invalidate_owner(2) == 3 and len(g) == 0
    # absorb: a digest with matching geometry folds in; mismatched is a no-op
    g.absorb(1, {"block_tokens": 4, "paths": [list(range(8))]})
    assert g.lookup(list(range(8)))[0] == 1
    g.absorb(3, {"block_tokens": 16, "paths": [list(range(64))]})
    assert g.lookup(list(range(64)))[0] == 1  # still the 8-token entry
    # LRU cap: the oldest untouched prefixes fall off
    for start in range(100, 100 + 8 * 4, 4):
        g.record(list(range(start, start + 4)), owner=0)
    assert len(g) == 8


def parse_replica_classes_test():
    assert parse_replica_classes("") == []
    assert parse_replica_classes("prefill:1,decode:2") \
        == ["prefill", "decode", "decode"]
    assert parse_replica_classes("decode, prefill") == ["decode", "prefill"]
    for bad in ("chonk:2", "prefill:0", "prefill:x", "prefill:-1"):
        with pytest.raises(ValueError):
            parse_replica_classes(bad)


# --------------------------------------------------- router dispatch (fakes)

def _disagg_router(classes, transport, n=3, **kw):
    reps = [Replica(i, 9000 + i, clock=lambda: 0.0) for i in range(n)]
    return Router(reps, transport=transport, clock=lambda: 0.0,
                  classes=classes, block_tokens=4, **kw), reps


def _tokens(n):
    return list(range(1, n + 1))


class _Fabric:
    """Fake replica fabric: records every (replica, path, op) call and
    answers /kv/blocks + /token_completion like a healthy replica."""

    def __init__(self):
        self.calls = []
        self.fail = set()       # replica indices that refuse connections
        self.empty_export = set()

    def __call__(self, replica, path, body, timeout, headers=None):
        op = body.get("op") if path == KV_BLOCKS_PATH else None
        self.calls.append((replica.index, path, op))
        if replica.index in self.fail:
            raise ConnectionRefusedError(f"replica {replica.index} down")
        if path == KV_BLOCKS_PATH:
            if op == "export":
                if replica.index in self.empty_export:
                    return 200, {"version": 1, "block_tokens": 4,
                                 "blocks": []}
                toks = body["tokens"]
                return 200, {
                    "version": 1, "block_tokens": 4,
                    "blocks": [{"key": toks[i:i + 4],
                                "leaves": {"target/k": {"bytes": 64}}}
                               for i in range(0, len(toks), 4)]}
            if op == "import":
                return 200, {"injected": len(body.get("blocks") or []),
                             "skipped": 0}
            if op == "index":
                return 200, {"block_tokens": 4, "paths": []}
        return 200, {"tokens": [7], "replica": replica.index}

    def forwards(self, kind=None):
        return [(i, p, o) for i, p, o in self.calls
                if (kind is None or o == kind)]


def disagg_miss_then_migrate_then_route_to_owner_test():
    """The full lifecycle: a cold prefix goes to the prefill class (miss),
    the next request migrates the blocks to a decode replica, and the
    third routes straight to that owner — no second transfer."""
    fab = _Fabric()
    router, reps = _disagg_router(["prefill", "decode", "decode"], fab)
    toks = _tokens(9)                        # 2 whole blocks + 1
    out = router.forward("/token_completion", {"tokens": toks})
    assert out["replica"] == 0               # prefill class owns the cold run
    assert router.gindex.lookup(toks)[0] == 0
    fab.calls.clear()
    out = router.forward("/token_completion", {"tokens": toks})
    assert out["replica"] in (1, 2)          # answered by a decode replica
    assert fab.forwards("export") == [(0, KV_BLOCKS_PATH, "export")]
    assert [i for i, _, o in fab.forwards("import")] == [out["replica"]]
    assert router.gindex.lookup(toks)[0] == out["replica"]
    fab.calls.clear()
    out2 = router.forward("/token_completion", {"tokens": toks})
    assert out2["replica"] == out["replica"]  # route-to-owner
    assert fab.forwards("export") == []       # blocks already live there


def disagg_short_prompt_skips_prefill_class_test():
    """Sub-block prompts carry nothing transferable: they go straight to
    the decode class so long decodes never queue behind prefills."""
    fab = _Fabric()
    router, _ = _disagg_router(["prefill", "decode", "decode"], fab)
    out = router.forward("/token_completion", {"tokens": [1, 2, 3]})
    assert out["replica"] in (1, 2)
    assert fab.forwards("export") == []


def disagg_shallow_hit_treated_as_cold_test():
    """A hit covering no more than half the span (typically a shared
    system head) is prefill-class work: migrating the sliver would move
    the heavy prefill onto a decode replica."""
    fab = _Fabric()
    router, _ = _disagg_router(["prefill", "decode", "decode"], fab)
    router.gindex.record(_tokens(4), owner=1)   # only the shared head
    out = router.forward("/token_completion", {"tokens": _tokens(13)})
    assert out["replica"] == 0                  # prefill class, no migration
    assert fab.forwards("export") == []
    assert router.gindex.lookup(_tokens(13))[0] == 0  # re-learned deeper


def disagg_owner_breaker_open_cold_fallback_test():
    """A hit naming an owner whose breaker is OPEN degrades to cold
    prefill elsewhere and drops the stale entries — never a 500."""
    fab = _Fabric()
    router, reps = _disagg_router(["prefill", "decode", "decode"], fab)
    toks = _tokens(9)
    router.gindex.record(toks, owner=1)
    for _ in range(3):
        reps[1].breaker.record_failure()
    assert reps[1].breaker.tick() == "open"
    out = router.forward("/token_completion", {"tokens": toks})
    assert out["replica"] != 1
    assert router.gindex.lookup(toks)[0] == out["replica"]  # re-learned


def disagg_migration_failure_cold_fallback_test():
    """The owner dying mid-stream (export leg refused) must not surface:
    the decode replica cold-prefills, the dead owner's entries drop."""
    fab = _Fabric()
    router, reps = _disagg_router(["prefill", "decode", "decode"], fab)
    toks = _tokens(9)
    router.forward("/token_completion", {"tokens": toks})  # owner: replica 0
    fab.fail.add(0)
    fab.calls.clear()
    out = router.forward("/token_completion", {"tokens": toks})
    assert out["replica"] in (1, 2)
    assert router.gindex.lookup(toks)[0] == out["replica"]
    # an owner whose tree already evicted the blocks (empty export) also
    # degrades cleanly
    fab.fail.clear()
    router.gindex.record(toks, owner=0)
    fab.empty_export.add(0)
    out = router.forward("/token_completion", {"tokens": toks})
    assert out["replica"] in (1, 2)


def disagg_all_replicas_open_still_503_test():
    fab = _Fabric()
    router, reps = _disagg_router(["prefill", "decode"], fab, n=2)
    for rep in reps:
        for _ in range(3):
            rep.breaker.record_failure()
    with pytest.raises(HTTPStatusError) as exc:
        router.forward("/token_completion", {"tokens": _tokens(9)})
    assert exc.value.status == 503


def disagg_index_sync_absorbs_replica_digests_test():
    """sync_global_index folds each replica's /kv/blocks index digest in
    on the poll cadence (self-throttled), so restarts and evictions
    reconcile without request traffic."""
    calls = []

    def transport(replica, path, body, timeout, headers=None):
        calls.append((replica.index, body.get("op")))
        if replica.index == 1:
            return 200, {"block_tokens": 4, "paths": [_tokens(8)]}
        return 200, {"block_tokens": 4, "paths": []}

    clock = [0.0]
    reps = [Replica(i, 9000 + i, clock=lambda: clock[0]) for i in range(2)]
    router = Router(reps, transport=transport, clock=lambda: clock[0],
                    classes=["prefill", "decode"], block_tokens=4,
                    index_sync_interval_s=5.0)
    assert router.sync_global_index() == 2
    assert router.gindex.lookup(_tokens(8))[0] == 1
    assert router.sync_global_index() == 0   # throttled
    clock[0] += 6.0
    assert router.sync_global_index() == 2


def symmetric_router_unchanged_test():
    """No classes (or a single class) => gindex is None and forward never
    touches /kv/blocks — the symmetric tier is byte-identical to today."""
    fab = _Fabric()
    router, _ = _disagg_router(None, fab)
    assert router.gindex is None and not router.disagg
    router.forward("/token_completion", {"tokens": _tokens(9)})
    assert all(p != KV_BLOCKS_PATH for _, p, _ in fab.calls)
    router2, _ = _disagg_router(["decode", "decode", "decode"], fab)
    assert router2.gindex is None


# ------------------------------------------------------- REST two replicas

def _spawn_rest(iface, port):
    from homebrewnlp_tpu.infer import rest_api
    stop = threading.Event()
    t = threading.Thread(target=rest_api.serve, args=(iface.params, iface),
                         kwargs={"port": port, "isolate": True,
                                 "stop": stop}, daemon=True)
    t.start()
    return stop, t


def _post(port, path, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    for _ in range(240):
        try:
            with urllib.request.urlopen(req, timeout=timeout) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())
        except (ConnectionError, urllib.error.URLError, OSError):
            time.sleep(0.25)
    raise TimeoutError(path)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.slow
def kv_blocks_rest_roundtrip_two_replicas_test():
    """The real seam: two isolated serving deployments, blocks exported
    over HTTP from the replica that prefilled and injected into the other,
    whose completion then answers bit-identically having skipped prefill —
    and both hbnlp_disagg_* replica counters move."""
    prompt = list(range(1, 17)) + [25]
    # ONE interface for both deployments (a second in-process Model would
    # renumber scope parameters): each serve() builds its own executor, so
    # the pools/trees are fully independent — exactly a replica pair's
    # state, minus the process boundary
    iface = _interface(serve_engine="continuous", serve_slots=2,
                       serve_batch_size=2, kv_paging="on",
                       kv_block_tokens=4)
    want = [int(x) for x in iface.complete_tokens(
        np.asarray(prompt, np.int32), 0.0, 6)]
    pa, pb = _free_port(), _free_port()
    # stagger the deployments: tracing is not concurrency-safe (scope
    # naming is a process-global counter), so B starts only after A's
    # warm-up compile answered /health — real replicas are processes and
    # never share a tracer
    stop_a, ta = _spawn_rest(iface, pa)
    stop_b = tb = None
    try:
        status, health = _post(pa, "/health", {})
        assert status == 200 and health["engine"]["kv_transfer"]
        stop_b, tb = _spawn_rest(iface, pb)
        status, _ = _post(pb, "/health", {})
        assert status == 200
        status, out = _post(pa, "/token_completion",
                            {"tokens": prompt, "max_tokens": 6,
                             "temperature": 0.0})
        assert status == 200 and out["tokens"] == want
        status, payload = _post(pa, KV_BLOCKS_PATH,
                                {"op": "export", "tokens": prompt})
        assert status == 200 and len(payload["blocks"]) == 4
        status, res = _post(pb, KV_BLOCKS_PATH, dict(payload, op="import"))
        assert status == 200 and res["injected"] == 4
        status, digest = _post(pb, KV_BLOCKS_PATH, {"op": "index"})
        assert status == 200 and prompt[:16] in digest["paths"]
        status, out_b = _post(pb, "/token_completion",
                              {"tokens": prompt, "max_tokens": 6,
                               "temperature": 0.0})
        assert status == 200 and out_b["tokens"] == want
        # a corrupt import answers 400, not a 500 or a silent injection
        # (fresh keys — a replayed key would hit the existing-child-wins
        # skip before validation ever sees the corrupt bytes)
        bad = json.loads(json.dumps(payload))
        for blk in bad["blocks"]:
            blk["key"] = [t + 100 for t in blk["key"]]
        name = sorted(bad["blocks"][0]["leaves"])[0]
        meta = bad["blocks"][0]["leaves"][name]
        raw = bytearray(base64.b64decode(meta["data"]))
        raw[0] ^= 0xFF
        meta["data"] = base64.b64encode(bytes(raw)).decode("ascii")
        status, err = _post(pb, KV_BLOCKS_PATH, dict(bad, op="import"))
        assert status == 400, err
        assert "verification" in err.get("error", "") \
            or "truncated" in err.get("error", ""), err
        for port, series in ((pa, "hbnlp_disagg_exported_blocks_total"),
                             (pb, "hbnlp_disagg_injected_blocks_total")):
            req = urllib.request.Request(f"http://127.0.0.1:{port}/metrics")
            with urllib.request.urlopen(req, timeout=30) as resp:
                text = resp.read().decode()
            assert f"{series} 4" in text, text[:2000]
    finally:
        stop_a.set()
        if stop_b is not None:
            stop_b.set()
        ta.join(timeout=15)
        if tb is not None:
            tb.join(timeout=15)
    assert not ta.is_alive()
    assert tb is not None and not tb.is_alive()
