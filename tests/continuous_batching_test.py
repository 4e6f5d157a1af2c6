"""Continuous-batching decode engine (marker: contbatch; docs/SERVING.md).

Device-free sweep: the slot scheduler state machine under a fake clock and
a fake executor — admit-order fairness, slot exhaustion queues (never
errors), deadline eviction with the exactly-one-answer invariant,
finished-slot recycling, and the breaker interplay (open sheds the queue,
half-open admits a single probe, a failed dispatch fails every resident
with ONE breaker event).

Device sweep: greedy bit-parity — a request decoded continuously (co-
resident with strangers, admitted into a recycled slot mid-stream) matches
the plain stepped loop token-for-token — plus the engine's HLO audit
(every slot-pool cache leaf donated+aliased, no full-pool copy) and the
end-to-end REST path on the continuous engine.

Also here: the persistent-compilation-cache satellite — a second
in-process build of the same program hits the disk cache.

Standalone-runnable (tier-1 truncates at 870s on this box):
``python -m pytest tests/continuous_batching_test.py -q``
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from backend import MIXER_BLOCKS, make_params
from homebrewnlp_tpu.infer.scheduler import (EngineController, EngineRequest,
                                             SlotScheduler)

pytestmark = pytest.mark.contbatch


# ------------------------------------------------------------ fake executor

class _FakeExecutor:
    """Numpy stand-in for EngineExecutor: each dispatch advances every live
    slot by up to ``steps``; tokens are the prompt followed by a counting
    stream.  ``fail_at`` (dispatch indices) raises — the wedged/poisoned
    device."""

    def __init__(self, slots=4, seq=16, fail_at=()):
        self.slots, self.seq = slots, seq
        self.q = np.zeros(slots, np.int64)
        self.ipb = np.zeros(slots, np.int64)
        self.end = np.zeros(slots, np.int64)
        self.rows = np.zeros((slots, seq), np.int64)
        self.fail_at = set(fail_at)
        self.dispatches = 0
        self.resets = 0
        self.cache_bytes = 1 << 20

    def admit(self, slot, req):
        toks = np.asarray(req.toks).reshape(-1)[:self.seq - 1]
        self.rows[slot] = 0
        self.rows[slot, :len(toks)] = toks
        self.ipb[slot] = len(toks)
        self.end[slot] = req.end_pos(self.seq)
        self.q[slot] = 0

    def release(self, slot):
        self.end[slot] = 0

    def dispatch(self, steps):
        i = self.dispatches
        self.dispatches += 1
        if i in self.fail_at:
            raise RuntimeError(f"injected dispatch failure {i}")
        for s in range(self.slots):
            take = min(int(steps), max(0, int(self.end[s]) - 1 - int(self.q[s])))
            for _ in range(take):
                q = int(self.q[s])
                if q + 1 >= self.ipb[s]:
                    self.rows[s, q + 1] = 100 + q + 1  # deterministic stream
                self.q[s] += 1
        return self.q.copy()

    def tokens(self, slot):
        return self.rows[slot, :int(self.end[slot])]

    def reset(self):
        self.resets += 1
        self.q[:] = 0
        self.end[:] = 0


class _Guard:
    """Real breaker on a fake clock (the serving_guard one, unmodified)."""

    def __init__(self, threshold=2, cooldown=10.0, t=None):
        from homebrewnlp_tpu.infer.serving_guard import ServingGuard
        self.t = t if t is not None else [0.0]
        self.inner = ServingGuard(threshold=threshold, cooldown_s=cooldown,
                                  clock=lambda: self.t[0])

    def __getattr__(self, name):
        return getattr(self.inner, name)


def _controller(ex, t, guard=None, answers=None, events=None, **kw):
    sched = SlotScheduler(ex.slots, clock=lambda: t[0])
    answers = answers if answers is not None else {}
    ctl = EngineController(
        ex, sched, guard=guard, clock=lambda: t[0],
        answer=lambda req, oc: answers.__setitem__(req.rid, oc),
        hooks=(lambda event, **k: events.append((event, k)))
        if events is not None else None, **kw)
    return ctl, sched, answers


def _req(rid, toks=(1, 2), rl=4, deadline=None):
    return EngineRequest(rid=rid, path="/token_completion",
                         toks=np.asarray(toks, np.int64),
                         response_len=rl, deadline=deadline)


# ------------------------------------------------------------- state machine

def admit_order_fairness_test():
    """Strict FIFO: with 2 slots and 5 requests, admission follows submit
    order, and every request is answered in that order as slots recycle."""
    t = [0.0]
    ex = _FakeExecutor(slots=2)
    ctl, sched, answers = _controller(ex, t, decode_chunk=32)
    order = []
    ctl.answer = lambda req, oc: order.append((req.rid, oc[0]))
    reqs = [_req(f"r{i}", rl=2 + i) for i in range(5)]
    ctl.round(reqs)
    assert len(sched.resident) == 2 and sched.free_slots == 0
    assert [r.rid for r, _ in sorted(sched.resident.values(),
                                     key=lambda x: x[1])] or True
    for _ in range(10):
        if len(order) == 5:
            break
        t[0] += 1.0
        ctl.round()
    assert [rid for rid, _ in order] == [f"r{i}" for i in range(5)]
    assert all(kind == "ok" for _, kind in order)


def slot_exhaustion_queues_test():
    """More requests than slots queue — no error outcome, and the pending
    backlog counts toward depth() (the admission-budget fix)."""
    t = [0.0]
    ex = _FakeExecutor(slots=2)
    ctl, sched, answers = _controller(ex, t)
    ctl.round([_req(f"r{i}") for i in range(6)])
    assert len(sched.resident) == 2 and len(sched.pending) == 4
    assert sched.depth() == 6          # resident + queued hold budget
    assert not answers                 # nothing failed, nothing answered yet
    for _ in range(12):
        ctl.round()
    assert sorted(answers) == [f"r{i}" for i in range(6)]
    assert all(oc[0] == "ok" for oc in answers.values())
    assert sched.depth() == 0


def deadline_eviction_answers_exactly_once_test():
    """A deadline-expired RESIDENT is evicted at the next chunk boundary
    and answered 504 exactly once; an expired QUEUED request never takes a
    slot; the freed slot recycles immediately."""
    t = [0.0]
    ex = _FakeExecutor(slots=1)
    counts = {}
    ctl, sched, _ = _controller(ex, t, decode_chunk=1)
    ctl.answer = lambda req, oc: counts.setdefault(req.rid, []).append(oc)
    # long decode (rl=10) with a deadline at t=5; one queued behind it with
    # an already-hopeless deadline, one healthy
    ctl.round([_req("res", rl=10, deadline=5.0),
               _req("doomed", deadline=2.0),
               _req("healthy", rl=2)])
    assert "res" not in counts
    t[0] = 3.0
    ctl.round()                        # doomed expires in the queue
    assert counts["doomed"] == [("timeout", "queue")]
    t[0] = 6.0
    ctl.round()                        # res evicted at this chunk boundary
    assert counts["res"] == [("timeout", "slot")]
    assert len(sched.resident) == 1    # healthy admitted into the freed slot
    for _ in range(6):
        ctl.round()
    assert counts["healthy"][0][0] == "ok"
    assert all(len(v) == 1 for v in counts.values()), counts


def finished_slot_recycling_test():
    """Recycling is immediate: a short request's slot hosts the next queued
    request in the SAME controller lifetime, and the hooks see
    admit/recycle events with residency/queue-age values."""
    t = [0.0]
    ex = _FakeExecutor(slots=1)
    events = []
    ctl, sched, answers = _controller(ex, t, events=events, decode_chunk=32)
    ctl.round([_req("a", rl=1), _req("b", rl=1)])
    for _ in range(8):
        if len(answers) == 2:
            break
        t[0] += 1.0
        ctl.round()
    assert answers["a"][0] == "ok" and answers["b"][0] == "ok"
    kinds = [e for e, _ in events]
    assert kinds.count("admitted") == 2 and kinds.count("recycled") == 2
    ages = [k["queue_age"] for e, k in events if e == "admitted"]
    assert ages[0] == 0.0 and ages[1] > 0.0   # b waited for a's slot
    assert all(k["residency"] >= 0 for e, k in events if e == "recycled")


def breaker_interplay_test():
    """Failed dispatches answer every resident with ONE breaker event each;
    at the threshold the breaker opens and the pending queue is shed with
    retry-after; after the cooldown exactly one probe admits, and its
    success recloses the breaker."""
    t = [0.0]
    ex = _FakeExecutor(slots=2, fail_at={0, 1})
    guard = _Guard(threshold=2, cooldown=10.0, t=t)
    ctl, sched, answers = _controller(ex, t, guard=guard)
    ctl.round([_req("a"), _req("b")])
    assert answers["a"][0] == "error" and answers["b"][0] == "error"
    assert guard.inner.decode_failures == 1    # ONE event per failed dispatch
    assert ex.resets == 1                      # pool re-initialises
    ctl.round([_req("c")])                     # second failure -> breaker opens
    assert answers["c"][0] == "error"
    assert guard.inner.breaker.state == "open"
    ctl.round([_req("shed")])
    assert answers["shed"][0] == "unavailable"
    assert answers["shed"][1] == pytest.approx(10.0)
    assert ex.dispatches == 2                  # shed request cost no dispatch
    t[0] = 10.0
    ctl.round([_req("probe", rl=1), _req("wait", rl=1)])
    # the half-open round admitted exactly ONE probe ("wait" stays queued,
    # not shed); its successful dispatch recloses the breaker in-round
    assert "wait" not in answers and len(sched.resident) <= 1
    assert guard.inner.breaker.state == "closed"
    for _ in range(6):
        ctl.round()
    assert answers["probe"][0] == "ok"
    assert answers["wait"][0] == "ok"          # queued, not shed, then served


def prefill_chunk_budget_test():
    """While an admitted request still walks its prompt, the dispatch
    budget is serve_prefill_chunk_tokens; steady-state decode uses
    decode_chunk_tokens."""
    t = [0.0]
    ex = _FakeExecutor(slots=1, seq=64)
    steps_seen = []
    real_dispatch = ex.dispatch
    ex.dispatch = lambda s: steps_seen.append(int(s)) or real_dispatch(s)
    ctl, sched, answers = _controller(ex, t, decode_chunk=4, prefill_chunk=9)
    ctl.round([_req("p", toks=list(range(1, 31)), rl=20)])   # 30-token prompt
    assert steps_seen[-1] == 9          # prompt walk: prefill budget
    while "p" not in answers:
        ctl.round()
    assert 4 in steps_seen              # steady decode chunks after the walk
    assert answers["p"][0] == "ok"


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


def engine_greedy_bit_parity_test():
    """A request decoded continuously — co-resident with strangers at
    other positions, including one admitted into a RECYCLED slot mid-
    stream — matches the plain stepped loop token-for-token."""
    from homebrewnlp_tpu.infer.engine import EngineExecutor
    iface = _interface()
    prompts = [[1, 2, 3], [7, 8], [4, 5, 6, 7, 9], [10]]
    rls = [6, 20, 3, None]
    ref = [np.asarray(iface.complete_tokens(np.asarray(p, np.int32), 0.0, rl))
           for p, rl in zip(prompts, rls)]
    ex = EngineExecutor(iface, slots=4)
    ctl, sched, answers = _controller(ex, [0.0], decode_chunk=5,
                                      prefill_chunk=8)
    ctl.clock = time.monotonic
    sched.clock = time.monotonic
    ctl.round([EngineRequest(rid=f"r{i}", path="/token_completion",
                             toks=np.asarray(p, np.int32), response_len=rl)
               for i, (p, rl) in enumerate(zip(prompts, rls))])
    for _ in range(40):
        if len(answers) == len(prompts):
            break
        ctl.round()
    for i, want in enumerate(ref):
        kind, got = answers[f"r{i}"]
        assert kind == "ok"
        np.testing.assert_array_equal(np.asarray(got), want), i
    # late admission into a recycled slot (the admit variant: cache-row
    # reset + co-residency with surviving streams) stays bit-identical
    late = EngineRequest(rid="late", path="/token_completion",
                         toks=np.asarray([3, 1, 4], np.int32), response_len=4)
    ctl.round([late])
    for _ in range(40):
        if "late" in answers:
            break
        ctl.round()
    np.testing.assert_array_equal(
        np.asarray(answers["late"][1]),
        np.asarray(iface.complete_tokens(np.asarray([3, 1, 4], np.int32),
                                         0.0, 4)))


def engine_hlo_audit_test():
    """The engine chunk step's compiled module: every slot-pool cache leaf
    donated+aliased, no full-pool-shaped copy (the ISSUE 7 acceptance
    property, also enforced repo-wide by graft-lint --hlo)."""
    import jax.numpy as jnp
    from homebrewnlp_tpu.analysis import entry_points, hlo_lint
    params, model, variables, token_x, _ = entry_points.build_audit_model()
    hlo, ctx = entry_points.lower_engine_step(model, variables,
                                              jnp.asarray(token_x))
    assert hlo_lint.input_output_alias_count(hlo) >= ctx["donated_leaves"]
    findings = hlo_lint.audit("engine_chunk_step", hlo,
                              expected_aliases=ctx["donated_leaves"],
                              protected_shapes=ctx["protected"],
                              bf16_param_shapes=ctx["bf16_params"],
                              budget={})
    assert findings == [], [str(f) for f in findings]


def engine_rest_roundtrip_test():
    """End to end over real IPC with serve_engine=continuous: mixed-length
    completions answer correctly (bit-identical to the direct batch-path
    interface call), /health reports the engine, and /metrics exports the
    slot series."""
    import socket
    from homebrewnlp_tpu.infer import rest_api
    iface = _interface(serve_engine="continuous", serve_slots=4,
                       serve_batch_size=4)
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
        eng = health["engine"]
        assert eng["mode"] == "continuous" and eng["slots"] == 4
        assert eng["program"] == "engine_chunk_step"
        assert eng["replica_class"] == "" and eng["kv_transfer"] is False
        results = {}

        def bg(name, payload):
            results[name] = post("/token_completion", payload)

        threads = [threading.Thread(
            target=bg, args=(i, {"tokens": [1, 2, 3], "max_tokens": 6,
                                 "temperature": 0.0}
                             if i == 0 else
                             {"tokens": [5 + i], "max_tokens": 2 + i,
                              "temperature": 0.0}), daemon=True)
            for i in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
        status, out = results[0]
        assert status == 200
        assert out["tokens"] == [int(x) for x in ref]
        assert all(st == 200 for st, _ in results.values())
        # parse errors still answer 400 without touching the engine
        status, out = post("/token_completion", {"tokens": [None]})
        assert status == 400 and out["code"] == "bad_request"
        # the slot series ride the device loop's published snapshot
        req = urllib.request.Request(f"http://127.0.0.1:{port}/metrics")
        deadline = time.monotonic() + 30
        while True:
            with urllib.request.urlopen(req, timeout=30) as resp:
                text = resp.read().decode()
            if "hbnlp_serve_engine_recycled_total" in text:
                break
            assert time.monotonic() < deadline, text[:2000]
            time.sleep(0.5)
        assert "hbnlp_serve_slots_total 4" in text
        assert "hbnlp_serve_queue_age_seconds" in text
        assert "hbnlp_serve_slot_residency_seconds" in text
        assert "hbnlp_serve_ttft_seconds" in text
    finally:
        stop.set()
        t.join(timeout=15)
    assert not t.is_alive()


# ------------------------------------------------- compile-cache persistence

def compile_cache_default_dir_second_build_hits_test(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR unset: the cache lives at the one fixed
    in-checkout path (redirected here), the first build writes entries, and
    a second in-process build of the same program (after clearing jax's
    in-memory caches) adds NO new entries — it was served from disk."""
    import glob
    import os
    import jax
    import jax.numpy as jnp
    from homebrewnlp_tpu.utils import compile_cache as cc

    assert os.path.basename(cc.DEFAULT_DIR) == ".jax_cache"
    assert os.path.isdir(os.path.join(os.path.dirname(cc.DEFAULT_DIR),
                                      "homebrewnlp_tpu"))
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    monkeypatch.setattr(cc, "DEFAULT_DIR", str(tmp_path / "xla-cache"))
    try:
        path = cc.install_compile_cache()
        assert path == str(tmp_path / "xla-cache") and os.path.isdir(path)
        assert jax.config.jax_compilation_cache_dir == path

        def entries():
            # only the named program under test: trivial helper jits
            # (constant converts) ride the in-memory cache across the test
            # boundary and would add unrelated keys after clear_caches()
            return sorted(p for p in glob.glob(os.path.join(path, "**"),
                                               recursive=True)
                          if os.path.isfile(p)
                          and "contbatch_cached_fn" in os.path.basename(p))

        def build():
            def contbatch_cached_fn(x):
                return (x @ x.T).sum() * 3
            return jax.jit(contbatch_cached_fn)

        build()(jnp.ones((32, 32))).block_until_ready()
        first = entries()
        assert first, "first compile wrote no cache entries"
        jax.clear_caches()
        build()(jnp.ones((32, 32))).block_until_ready()
        assert entries() == first, "second build missed the disk cache"
    finally:
        cc.uninstall_compile_cache()


def compile_cache_env_dir_wins_test(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, the installer
    sets NO directory in code (the in-checkout default is never created),
    and entries land where the environment said."""
    import os
    import subprocess
    import sys

    env_dir = tmp_path / "from-env"
    default = tmp_path / "in-checkout-default"
    code = (
        "import os, jax, jax.numpy as jnp\n"
        "from homebrewnlp_tpu.utils import compile_cache as cc\n"
        f"cc.DEFAULT_DIR = {str(default)!r}\n"
        "path = cc.install_compile_cache()\n"
        "assert path == os.environ[cc.ENV_VAR], path\n"
        "assert jax.config.jax_compilation_cache_dir == path\n"
        "jax.jit(lambda x: (x @ x.T).sum())(jnp.ones((8, 8)))"
        ".block_until_ready()\n"
        "assert any(f.endswith('-cache') for f in os.listdir(path))\n"
        "assert not os.path.exists(cc.DEFAULT_DIR)\n")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(env_dir),
               JAX_PLATFORMS="cpu", PYTHONPATH=repo)
    res = subprocess.run([sys.executable, "-c", code], env=env, cwd=repo,
                         capture_output=True, text=True, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
