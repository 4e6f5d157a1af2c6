"""Paged KV cache + radix prefix sharing (marker: pagedkv; docs/SERVING.md).

Device-free sweep: the BlockPool/RadixIndex lifecycle state machines —
refcounts with hard-error double-free negative controls, reservation
accounting, LRU eviction of refcount-0 leaves only, partial-prefix
matching — and the scheduler's fits-gate (block exhaustion QUEUES at the
FIFO head, never errors or skips).

Device sweep: greedy bit-parity of the paged engine against the plain
stepped loop — cold admissions, admissions into RECLAIMED (dirty) blocks
on an undersized pool, and prefix-HIT admissions whose prefill is skipped
over the shared span — plus copy-on-write leaving the shared parent block
bit-unchanged on device, exact free-accounting at release, the paged
chunk step's HLO audit (every pool leaf aliased, no full-pool copy), the
``kv_paging`` knob resolution matrix, and the REST path with the
``hbnlp_kv_*`` gauges.

Standalone-runnable:
``python -m pytest tests/paged_kv_test.py -q``
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from backend import MIXER_BLOCKS, make_params
from homebrewnlp_tpu.infer.paged import BlockPool, RadixIndex
from homebrewnlp_tpu.infer.scheduler import (EngineController, EngineRequest,
                                             SlotScheduler)

pytestmark = pytest.mark.pagedkv


# ---------------------------------------------------------- pool lifecycle

def block_pool_lifecycle_test():
    """alloc/addref/deref/reclaim accounting, and the double-free negative
    controls: deref of a freed or zero-ref block raises, reclaim of a free
    or still-referenced block raises."""
    pool = BlockPool(4)
    assert pool.free_count == 4 and pool.live_count == 0
    a = pool.alloc()
    b = pool.alloc()
    assert pool.free_count == 2 and pool.live_count == 2
    pool.addref(a)
    assert pool.deref(a) == 1          # shared ref gone, owner remains
    assert pool.deref(a) == 0
    pool.reclaim(a)
    assert pool.free_count == 3
    # negative controls: every double-free shape must raise
    with pytest.raises(ValueError):
        pool.deref(a)                   # deref of a freed block
    with pytest.raises(ValueError):
        pool.reclaim(a)                 # reclaim twice
    with pytest.raises(ValueError):
        pool.reclaim(b)                 # reclaim of a live block
    assert pool.deref(b) == 0
    with pytest.raises(ValueError):
        pool.deref(b)                   # deref below zero
    with pytest.raises(ValueError):
        pool.addref(a)                  # addref of a freed block
    # reservations subtract from availability
    pool.reserve(2)
    assert pool.available() == pool.free_count - 2
    assert pool.available(evictable=1) == pool.free_count - 1
    pool.unreserve(5)                   # floors at zero
    assert pool.available() == pool.free_count


def radix_lookup_insert_partial_test():
    """Full-block path matching, partial (divergence-point) matching, and
    the existing-node-wins insert rule."""
    tree = RadixIndex(block_tokens=4)
    pool = BlockPool(8)
    b0, b1 = pool.alloc(), pool.alloc()
    n0 = tree.insert(None, (1, 2, 3, 4), b0)
    n1 = tree.insert(n0, (5, 6, 7, 8), b1)
    assert tree.holds(b0) and tree.holds(b1) and len(tree) == 2
    full, partial, d = tree.lookup([1, 2, 3, 4, 5, 6, 7, 8, 9])
    assert [n.block for n in full] == [b0, b1] and d == 0
    # divergence inside the second block: partial match at depth 2
    full, partial, d = tree.lookup([1, 2, 3, 4, 5, 6, 99, 98])
    assert [n.block for n in full] == [b0]
    assert partial is n1 and d == 2
    # no match at all
    full, partial, d = tree.lookup([9, 9, 9, 9])
    assert full == [] and partial is None and d == 0
    # an identical insert returns the EXISTING node; the caller's block
    # stays private (not tree-held)
    b2 = pool.alloc()
    again = tree.insert(None, (1, 2, 3, 4), b2)
    assert again is n0 and not tree.holds(b2)


def radix_lru_eviction_test():
    """evict_lru removes only refcount-0 LEAVES, oldest-touched first; a
    referenced or internal node survives."""
    tree = RadixIndex(block_tokens=2)
    pool = BlockPool(8)
    blocks = [pool.alloc() for _ in range(3)]
    n0 = tree.insert(None, (1, 2), blocks[0])
    tree.insert(n0, (3, 4), blocks[1])       # leaf under n0
    tree.insert(None, (9, 9), blocks[2])     # independent leaf
    for b in blocks:
        assert pool.deref(b) == 0            # all cache-resident
    assert tree.evictable_count(pool) == 3
    # touch the independent leaf so the n0-subtree leaf is LRU
    tree.lookup([9, 9])
    assert tree.evict_lru(pool)
    assert not tree.holds(blocks[1])         # leaf evicted, not internal n0
    assert pool.free_count == 6
    # a referenced leaf is not evictable
    pool.addref(blocks[2])
    tree.lookup([1, 2])                      # make (9,9) LRU again
    assert tree.evict_lru(pool)
    assert not tree.holds(blocks[0]) and tree.holds(blocks[2])
    assert not tree.evict_lru(pool)          # only the referenced one left


def scheduler_fits_gate_queues_at_head_test():
    """The fits-gate (block exhaustion) stops admission AT the FIFO head:
    nothing errors, nothing skips ahead, and admission resumes when
    capacity returns."""
    t = [0.0]
    sched = SlotScheduler(4, clock=lambda: t[0])
    capacity = [1]                           # admissions the "pool" can hold

    def fits(req):
        return len(sched.resident) < capacity[0]

    for i in range(3):
        sched.submit(EngineRequest(rid=f"r{i}", path="/token_completion",
                                   toks=np.asarray([1, 2])))
    admitted = sched.admit(fits=fits)
    assert [r.rid for _, r, _ in admitted] == ["r0"]
    assert sched.admit(fits=fits) == []      # r1 queued, r2 behind it
    assert [r.rid for r in sched.pending] == ["r1", "r2"]
    capacity[0] = 3
    admitted = sched.admit(fits=fits)
    assert [r.rid for _, r, _ in admitted] == ["r1", "r2"]


# ----------------------------------------------------------- device parity

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


def _paged_controller(iface, slots=4, block_tokens=4, pool_blocks=None,
                      decode_chunk=5, prefill_chunk=8):
    from homebrewnlp_tpu.infer.paged import PagedEngineExecutor
    ex = PagedEngineExecutor(iface, slots=slots, block_tokens=block_tokens,
                            pool_blocks=pool_blocks)
    answers = {}
    sched = SlotScheduler(ex.slots, clock=time.monotonic)
    ctl = EngineController(
        ex, sched, clock=time.monotonic, decode_chunk=decode_chunk,
        prefill_chunk=prefill_chunk,
        answer=lambda req, oc: answers.__setitem__(req.rid, oc))
    return ex, ctl, sched, answers


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


def paged_greedy_bit_parity_reclaimed_blocks_test():
    """Paged-vs-plain greedy bit-parity token-for-token: co-resident
    strangers at mixed positions, then THREE more admission waves on an
    UNDERSIZED pool (blocks cycle through the free list and the radix
    cache gets LRU-evicted), so late requests decode in reclaimed dirty
    blocks — parity must hold through all of it."""
    iface = _interface()
    # pool of 16 blocks = half the slot-engine equivalent (4 slots x 8)
    ex, ctl, sched, answers = _paged_controller(iface, pool_blocks=16)
    assert ex.sharing
    waves = [
        [([1, 2, 3], 6), ([7, 8], 12), ([4, 5, 6, 7, 9], 3), ([10], None)],
        [([3, 1, 4], 8), ([2, 7, 1, 8], 10)],
        [([11, 12, 13, 14, 15], 7), ([9], 20)],
    ]
    n = 0
    for wave in waves:
        reqs = [_req(f"r{n + i}", toks, rl)
                for i, (toks, rl) in enumerate(wave)]
        n += len(wave)
        _serve(ctl, answers, reqs)
    n = 0
    for wave in waves:
        for toks, rl in wave:
            want = np.asarray(iface.complete_tokens(
                np.asarray(toks, np.int32), 0.0, rl))
            kind, got = answers[f"r{n}"]
            assert kind == "ok", (n, kind)
            np.testing.assert_array_equal(np.asarray(got), want, str(n))
            n += 1
    stats = ex.pool_stats()
    assert stats["blocks_total"] == 16
    assert stats["blocks_in_use"] == 0       # everything released


def paged_int8_kv_parity_test():
    """int8 KV pools page too: the sibling per-row scale caches carry the
    same sequence axis, ride the same block tables, and greedy output
    stays bit-identical to the plain stepped loop — including through a
    prefix-hit admission (shared blocks hold identical int8 rows AND
    identical scales, by quantization determinism)."""
    iface = _interface(decode_cache_dtype="int8")
    ex, ctl, sched, answers = _paged_controller(iface)
    # both the int8 rows and their f32 scale siblings must be paged
    paged = [n for n, (_, sax) in ex.leaf_info.items() if sax is not None]
    assert any(n.endswith("_scale") for n in paged), ex.leaf_info
    sysp = list(range(1, 14))
    a, b = sysp + [40], sysp + [41, 42]
    _serve(ctl, answers, [_req("a", a, 8)])
    _serve(ctl, answers, [_req("b", b, 8)])
    assert ex.pool_stats()["prefix_hit_tokens"] > 0
    for rid, toks, rl in (("a", a, 8), ("b", b, 8)):
        np.testing.assert_array_equal(
            np.asarray(answers[rid][1]),
            np.asarray(iface.complete_tokens(np.asarray(toks, np.int32),
                                             0.0, rl)), rid)


def paged_prefix_hit_skips_prefill_at_parity_test():
    """Two requests sharing a long system prompt: the second references
    the first's radix-cached blocks (prefix_hit_tokens grows, its q starts
    past the shared span — prefill skipped) and its output is BIT-IDENTICAL
    to a cold decode of the same prompt."""
    iface = _interface()
    ex, ctl, sched, answers = _paged_controller(iface)
    sysp = list(range(1, 17))                # 16 shared tokens, 4 blocks
    a, b = sysp + [21, 22], sysp + [23]
    _serve(ctl, answers, [_req("a", a, 6)])
    st0 = dict(ex.pool_stats())
    assert st0["prefix_hit_tokens"] == 0
    _serve(ctl, answers, [_req("b", b, 6)])
    st1 = ex.pool_stats()
    assert st1["prefix_hits"] == st0["prefix_hits"] + 1
    assert st1["prefix_hit_tokens"] - st0["prefix_hit_tokens"] == 16
    np.testing.assert_array_equal(
        np.asarray(answers["b"][1]),
        np.asarray(iface.complete_tokens(np.asarray(b, np.int32), 0.0, 6)))
    np.testing.assert_array_equal(
        np.asarray(answers["a"][1]),
        np.asarray(iface.complete_tokens(np.asarray(a, np.int32), 0.0, 6)))


def paged_cow_parent_blocks_bit_unchanged_test():
    """Copy-on-write at the divergence point: a child diverging INSIDE a
    shared block writes its own copy; the parent's physical block in the
    device pool stays bit-identical, and the child's output matches a cold
    decode."""
    iface = _interface()
    ex, ctl, sched, answers = _paged_controller(iface)
    parent = [5, 6, 7, 8, 9, 10]             # blocks: [5,6,7,8] + partial
    _serve(ctl, answers, [_req("parent", parent, 4)])
    st = ex.pool_stats()
    assert st["blocks_cached"] >= 1          # block (5,6,7,8) promoted
    # find the promoted block's physical id and snapshot its pool content
    full, _, _ = ex.tree.lookup(parent[:4])
    assert len(full) == 1
    phys = full[0].block

    def block_content():
        out = {}
        for name, leaf in ex._carry[2].items():
            baxis, sax = ex.leaf_info[name]
            if sax is None:
                continue
            out[name] = np.take(np.asarray(leaf), phys, axis=baxis).copy()
        return out

    before = block_content()
    assert before, "no paged leaves found"
    # child shares tokens 5,6 then diverges inside the first block
    child = [5, 6, 99, 98, 97]
    cow0 = ex.pool_stats()["cow_copies"]
    _serve(ctl, answers, [_req("child", child, 5)])
    assert ex.pool_stats()["cow_copies"] == cow0 + 1
    after = block_content()
    for name in before:
        np.testing.assert_array_equal(before[name], after[name], name)
    np.testing.assert_array_equal(
        np.asarray(answers["child"][1]),
        np.asarray(iface.complete_tokens(np.asarray(child, np.int32),
                                         0.0, 5)))


def paged_release_returns_exact_blocks_test():
    """Finishing a request returns exactly its non-shared blocks: private
    generation blocks land on the free list, fully-walked prompt blocks
    stay radix-cached (refcount 0, reclaimable), and shared parent blocks
    only lose the child's reference."""
    iface = _interface()
    ex, ctl, sched, answers = _paged_controller(iface)
    parent = list(range(1, 13))              # 12 prompt tokens = 3 blocks
    _serve(ctl, answers, [_req("p", parent, 8)])
    base = ex.pool_stats()
    assert base["blocks_in_use"] == 0
    # prompt blocks (1..8) cached; child references the first two
    full, _, _ = ex.tree.lookup(parent[:11])
    shared_ids = [n.block for n in full]
    assert len(shared_ids) == 2
    child = parent[:8] + [50, 51]            # shares 2 full blocks
    ex2_free_before = ex.pool.free_count
    _serve(ctl, answers, [_req("c", child, 6)])
    st = ex.pool_stats()
    # shared parents still cached with refcount back to 0, not freed
    for b in shared_ids:
        assert ex.tree.holds(b) and ex.pool.refcount(b) == 0
    assert st["blocks_in_use"] == 0
    # free + cached partition the pool exactly (nothing leaked)
    assert st["blocks_free"] + st["blocks_cached"] == st["blocks_total"]
    # the child's private non-prompt blocks came BACK to the free list:
    # free count only moved by what its own prompt left in the cache
    assert ex.pool.free_count >= ex2_free_before - 3


def paged_pool_exhaustion_queues_test():
    """An admission whose worst-case extent cannot be reserved QUEUES (the
    429/500-free invariant) and admits once the resident finishes."""
    iface = _interface()
    # pool = exactly one full-length request (8 blocks of 4)
    ex, ctl, sched, answers = _paged_controller(iface, pool_blocks=8)
    long_a = _req("a", [1, 2], None)         # end = seq: needs all 8
    long_b = _req("b", [3, 4], None)
    ctl.round([long_a, long_b])
    assert "a" not in answers and "b" not in answers
    assert len(sched.resident) == 1          # b queued on blocks, not slots
    assert sched.free_slots > 0
    for _ in range(120):
        if "a" in answers and "b" in answers:
            break
        ctl.round()
    assert answers["a"][0] == "ok" and answers["b"][0] == "ok"
    np.testing.assert_array_equal(
        np.asarray(answers["b"][1]),
        np.asarray(iface.complete_tokens(np.asarray([3, 4], np.int32),
                                         0.0, None)))


# --------------------------------------------------- resolution + HLO audit

def kv_paging_knob_resolution_test():
    """kv_paging=off resolves the plain slot engine (byte-identical
    serving), "on" the paged executor; the contradictions
    (batch engine + on, spec draft + paging) refuse loudly; "auto" falls
    back to the plain engine when the geometry cannot page."""
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.infer.engine import EngineExecutor
    from homebrewnlp_tpu.infer.paged import PagedEngineExecutor
    from homebrewnlp_tpu.infer.rest_api import _resolve_engine

    iface = _interface()

    def resolve(**kw):
        params = ModelParameter(iface.params, serve_slots=2, **kw)
        params.train = False
        return _resolve_engine(params, iface)

    off = resolve(kv_paging="off")
    assert type(off) is EngineExecutor
    on = resolve(kv_paging="on", kv_block_tokens=4)
    assert type(on) is PagedEngineExecutor
    with pytest.raises(RuntimeError):
        resolve(kv_paging="on", serve_engine="batch")
    with pytest.raises(RuntimeError):
        resolve(kv_paging="on", spec_decode="draft")
    # geometry the pool cannot carry: "auto" falls back, "on" refuses
    auto = resolve(kv_paging="auto", kv_block_tokens=7)  # 32 % 7 != 0
    assert type(auto) is EngineExecutor
    with pytest.raises(RuntimeError):
        resolve(kv_paging="on", kv_block_tokens=7)


def paged_hlo_audit_test():
    """The paged chunk step's compiled module: every block-pool leaf
    donated+aliased, no full-pool-shaped copy — the gather/scatter
    round-trip must not cost a resident duplicate of the pool."""
    import jax.numpy as jnp
    from homebrewnlp_tpu.analysis import entry_points, hlo_lint
    params, model, variables, token_x, _ = entry_points.build_audit_model()
    hlo, ctx = entry_points.lower_paged_step(model, variables,
                                             jnp.asarray(token_x))
    assert hlo_lint.input_output_alias_count(hlo) >= ctx["donated_leaves"]
    findings = hlo_lint.audit("paged_chunk_step", hlo,
                              expected_aliases=ctx["donated_leaves"],
                              protected_shapes=ctx["protected"],
                              bf16_param_shapes=ctx["bf16_params"],
                              budget={})
    assert findings == [], [str(f) for f in findings]


def paged_rest_roundtrip_test():
    """End to end over real IPC with kv_paging=on: completions answer
    bit-identically to the direct interface call, /health reports the
    paging geometry, and /metrics exports the hbnlp_kv_* block series."""
    import socket
    from homebrewnlp_tpu.infer import rest_api
    iface = _interface(serve_engine="continuous", serve_slots=4,
                       serve_batch_size=4, kv_paging="on",
                       kv_block_tokens=4)
    ref = np.asarray(iface.complete_tokens(np.asarray([1, 2, 3], np.int32),
                                           0.0, 6))
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    stop = threading.Event()
    t = threading.Thread(target=rest_api.serve,
                         args=(iface.params, iface),
                         kwargs={"port": port, "isolate": True, "stop": stop},
                         daemon=True)
    t.start()

    def post(path, payload, timeout=120):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}",
            data=json.dumps(payload).encode(),
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

    try:
        status, health = post("/health", {})
        assert status == 200
        engine = health["engine"]
        assert engine["mode"] == "continuous"
        paging = engine["paging"]
        assert paging["block_tokens"] == 4 and paging["sharing"]
        assert paging["blocks_total"] == 4 * (32 // 4)
        status, out = post("/token_completion",
                           {"tokens": [1, 2, 3], "max_tokens": 6,
                            "temperature": 0.0})
        assert status == 200 and out["tokens"] == [int(x) for x in ref]
        # a second identical prompt hits the prefix cache; same answer
        status, out2 = post("/token_completion",
                            {"tokens": [1, 2, 3], "max_tokens": 6,
                             "temperature": 0.0})
        assert status == 200 and out2["tokens"] == out["tokens"]
        req = urllib.request.Request(f"http://127.0.0.1:{port}/metrics")
        deadline = time.monotonic() + 30
        while True:
            with urllib.request.urlopen(req, timeout=30) as resp:
                text = resp.read().decode()
            if "hbnlp_kv_blocks_total" in text:
                break
            assert time.monotonic() < deadline, text[:2000]
            time.sleep(0.5)
        assert "hbnlp_kv_blocks_total 32" in text
        assert "hbnlp_kv_blocks_in_use" in text
        assert "hbnlp_kv_prefix_hit_tokens_total" in text
    finally:
        stop.set()
        t.join(timeout=15)
    assert not t.is_alive()
