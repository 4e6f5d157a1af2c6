"""Cost-attribution layer (marker: attribution; docs/OBSERVABILITY.md
'Cost attribution').

Cheap half: scope folding, the ledger regression check's negative controls
(an inflated ledger MUST fail the lint), and the serving TTFT/ITL/cache-
bandwidth recording driven through the real hook plumbing.

Expensive half (one audit-model build per module): the committed
``analysis/cost_ledger.json`` matches a fresh build.  Reading a device
trace is the benchmark's (``benchmark/trace/reduce.py``, tested on a v5e
fixture by ``benchmark/tests/trace_test.py``).
"""
import copy
import time

import numpy as np
import pytest

from backend import make_params
from homebrewnlp_tpu import telemetry
from homebrewnlp_tpu.analysis import cost_ledger

pytestmark = pytest.mark.attribution

@pytest.fixture
def fresh_registry():
    reg = telemetry.Registry()
    prev = telemetry.set_registry(reg)
    import homebrewnlp_tpu.infer.rest_api as ra
    saved = ra._SERVE_METRICS
    ra._SERVE_METRICS = None
    try:
        yield reg
    finally:
        ra._SERVE_METRICS = saved
        telemetry.set_registry(prev)


# ------------------------------------------------------------- scope folding

def scope_key_test():
    sk = cost_ledger.scope_key
    assert sk("jit(step_fn)/jit(main)/jvp(gpt0)/body0/while/body/"
              "block0_1_0/attention_1/abc,dcae->dbae/dot_general") \
        == "body/attention"
    # backward ops fold into the SAME per-block scope as forward
    assert sk("transpose(jvp(gpt0))/body0/while/body/block0_0_0/"
              "bottleneck_group_linear_0/dot_general") \
        == "body/bottleneck_group_linear"
    assert sk("jvp(gpt0)/input0/gather0/embed0/convert") == "input/embed"
    assert sk("jvp(gpt0)/input0/abcd,de->abce/dot_general") == "input"
    assert sk("gpt0/output0/embed0/orthogonal_var0/convert") \
        == "output/unembed"
    assert sk("gpt0/loss0/reduce_sum") == "loss"
    assert sk("jit(step_fn)/jit(main)/optimizer/mul") == "optimizer"
    assert sk("gpt0/body0/block0_1_0/attention_0/cache_write/"
              "dynamic_update_slice") == "decode/cache_write"
    assert sk("sampling/argmax") == "decode/sampling"
    assert sk("jit(step_fn)/jit(main)/mul") == "unscoped"


# ------------------------------------------- instruction table + event join

def ledger_missing_file_is_finding_test(tmp_path):
    f = cost_ledger.ledger_audit(path=str(tmp_path / "absent.json"),
                                 current={"entry_points": {}})
    assert len(f) == 1 and "missing" in f[0].message


def ledger_inflated_negative_control_test():
    """Acceptance: a synthetically inflated ledger entry MUST fail the
    regression check (and an identical one must pass)."""
    stored = cost_ledger.load_ledger()
    assert stored is not None, "analysis/cost_ledger.json must be committed"
    # the ledger covers exactly the audited entry points: the list lives in
    # analysis/entry_points.py, not in a copy here
    from homebrewnlp_tpu.analysis import entry_points
    assert set(stored["entry_points"]) == set(entry_points.ENTRY_POINTS)
    clean = cost_ledger.ledger_audit(current=copy.deepcopy(stored))
    assert clean == []
    bad = copy.deepcopy(stored)
    bad["entry_points"]["train_step"]["scopes"]["body/attention"]["flops"] \
        *= 2
    findings = cost_ledger.ledger_audit(current=bad)
    assert findings and findings[0].rule == "cost-ledger"
    assert "body/attention" in findings[0].message
    # a vanished scope is a finding too
    gone = copy.deepcopy(stored)
    gone["entry_points"]["train_step"]["scopes"].pop("body/attention")
    findings = cost_ledger.ledger_audit(current=gone)
    assert any("vanished" in f.message or "not in the committed" in f.message
               for f in findings)
    # ... and so is a whole entry point dropping out of the fresh build
    dropped = copy.deepcopy(stored)
    dropped["entry_points"].pop("eval_fn")
    findings = cost_ledger.ledger_audit(current=dropped)
    assert any(f.entry == "eval_fn" and "vanished" in f.message
               for f in findings)


def ledger_schema_test():
    """Every entry carries per-scope flops/bytes/shares/bound and a total;
    >= 5 distinct model scopes per entry (the attribution floor)."""
    stored = cost_ledger.load_ledger()
    for entry, tab in stored["entry_points"].items():
        assert {"flops", "bytes", "intensity", "bound"} <= set(tab["total"])
        assert len(tab["scopes"]) >= 5, (entry, list(tab["scopes"]))
        for scope, s in tab["scopes"].items():
            assert {"flops", "bytes", "flops_share", "bytes_share",
                    "intensity", "bound"} <= set(s), (entry, scope)
            assert s["bound"] in ("compute", "hbm")
    decode_scopes = stored["entry_points"]["decode_chunk_step"]["scopes"]
    assert "decode/sampling" in decode_scopes
    assert "decode/cache_write" in decode_scopes


# --------------------------------------- serving hook -> TTFT/ITL recording

def decode_progress_recording_test(fresh_registry):
    """rest_api._decode_progress turns sampler hook events into TTFT (one
    per co-batched request, from its own admission timestamp), ITL (per
    chunk) and the cache-bandwidth gauges."""
    import homebrewnlp_tpu.infer.rest_api as ra
    import homebrewnlp_tpu.infer.sampler as sampler_mod
    t0 = time.monotonic()
    with ra._decode_progress([t0 - 2.0, t0 - 1.0, None]):
        hook = sampler_mod.decode_progress_hook()
        assert hook is not None
        hook("chunk", dt=0.2, steps=4, cache_bytes=1 << 30)
        hook("first_token")
        hook("chunk", dt=0.1, steps=2, cache_bytes=1 << 30)
    assert sampler_mod.decode_progress_hook() is None  # restored
    snap = fresh_registry.snapshot()
    ttft = snap["hbnlp_serve_ttft_seconds"]["series"][()]
    assert sum(ttft["counts"]) == 3
    assert ttft["sum"] >= 3.0          # 2s + 1s + ~0s
    itl = snap["hbnlp_serve_itl_seconds"]["series"][()]
    assert sum(itl["counts"]) == 2
    assert abs(itl["sum"] - 0.1) < 0.02  # 0.2/4 + 0.1/2
    bps = snap["hbnlp_decode_cache_read_bytes_per_second"]["series"][()]
    assert abs(bps - (1 << 30) * 2 / 0.1) / bps < 0.01  # last chunk wins
    frac = snap["hbnlp_decode_cache_bw_fraction_of_peak"]["series"][()]
    assert frac > 0


def per_row_ttft_heterogeneous_prompts_test(fresh_registry):
    """Co-batched requests close TTFT individually: a row whose prompt is
    still being walked when the batch's first token fires must NOT record
    its TTFT yet (the short prompt's event closes only its own row), and a
    row never closes twice."""
    import homebrewnlp_tpu.infer.rest_api as ra
    t0 = time.monotonic()
    with ra._decode_progress([t0 - 1.0, t0 - 1.0]):
        import homebrewnlp_tpu.infer.sampler as sampler_mod
        hook = sampler_mod.decode_progress_hook()
        hook("first_token", rows=[0])
        snap = fresh_registry.snapshot()
        assert sum(snap["hbnlp_serve_ttft_seconds"]["series"][()]
                   ["counts"]) == 1
        hook("first_token", rows=[0, 1])    # row 0 already closed
    snap = fresh_registry.snapshot()
    ttft = snap["hbnlp_serve_ttft_seconds"]["series"][()]
    assert sum(ttft["counts"]) == 2


def retry_does_not_double_count_ttft_test(fresh_registry):
    """A failed batch attempt that already fired a row's first token must
    not contribute a SECOND TTFT sample from that row's per-item retry —
    the caller-shared ``closed`` flags carry the state across attempts,
    while a row the batch never reached still records from its retry."""
    import homebrewnlp_tpu.infer.rest_api as ra
    import homebrewnlp_tpu.infer.sampler as sampler_mod
    t0 = time.monotonic()
    flags = [False, False]
    with ra._decode_progress([t0 - 1.0, t0 - 1.0], closed=flags):
        sampler_mod.decode_progress_hook()("first_token", rows=[0])
    assert flags == [True, False]
    # batch decode failed after row 0's first token: per-row retries
    with ra._decode_progress([t0 - 1.0], closed=flags[0:1]):
        sampler_mod.decode_progress_hook()("first_token")
    with ra._decode_progress([t0 - 1.0], closed=flags[1:2]):
        sampler_mod.decode_progress_hook()("first_token")
    snap = fresh_registry.snapshot()
    assert sum(snap["hbnlp_serve_ttft_seconds"]["series"][()]
               ["counts"]) == 2


def stepped_per_row_first_token_test(fresh_registry):
    """The REAL stepped loop fires first_token per row at that row's own
    initial position: with prompts of length 4 and 20 (chunk 4), row 0's
    event lands chunks before row 1's."""
    import jax.numpy as jnp
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.infer import sampler

    params = make_params(vocab_size=64, sequence_length=32, depth=2,
                         heads=2, features_per_head=8, train_batch_size=2,
                         decode_loop="stepped", decode_chunk_tokens=4)
    model = Model(params)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 64, (2, 32, 1)).astype(np.int32)
    variables = {k: jnp.asarray(v) for k, v in model.init(
        {"token_x": jnp.asarray(tok), "token_y": jnp.asarray(tok)}).items()}
    events = []
    prev = sampler.set_decode_progress_hook(
        lambda ev, **kw: events.append((ev, dict(kw))))
    try:
        sampler.sample_text(model, variables, tok[:, :20, 0],
                            initial_pos=np.asarray([4, 20]),
                            temperature=0.0, end_iterations=28, seed=0)
    finally:
        sampler.set_decode_progress_hook(prev)
    firsts = [(i, kw["rows"]) for i, (ev, kw) in enumerate(events)
              if ev == "first_token"]
    assert [rows for _, rows in firsts] == [[0], [1]]
    assert firsts[0][0] < firsts[1][0], "row 1 must fire in a LATER chunk"


def stepped_zero_chunk_decode_still_fires_first_token_test():
    """A stepped decode that ends before ANY chunk runs (end_iterations
    at/below the prefill position) still closes one first_token per row at
    completion — otherwise the serving TTFT histogram silently drops
    exactly the cheapest requests and its quantiles bias upward."""
    import jax.numpy as jnp
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.infer import sampler

    params = make_params(vocab_size=64, sequence_length=32, depth=2,
                         heads=2, features_per_head=8, train_batch_size=2,
                         decode_loop="stepped", decode_chunk_tokens=4)
    model = Model(params)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 64, (2, 32, 1)).astype(np.int32)
    variables = {k: jnp.asarray(v) for k, v in model.init(
        {"token_x": jnp.asarray(tok), "token_y": jnp.asarray(tok)}).items()}
    events = []
    prev = sampler.set_decode_progress_hook(
        lambda ev, **kw: events.append((ev, dict(kw))))
    try:
        sampler.sample_text(model, variables, tok[:, :20, 0],
                            initial_pos=np.asarray([4, 20]),
                            temperature=0.0, end_iterations=4, seed=0)
    finally:
        sampler.set_decode_progress_hook(prev)
    rows = [kw["rows"] for ev, kw in events if ev == "first_token"]
    assert sorted(r for rs in rows for r in rs) == [0, 1], events


def decode_progress_hook_thread_isolated_test():
    """The hook is per-thread: concurrent in-process requests install and
    restore without swapping each other's hooks mid-decode (both serving
    modes run the decode on the installing thread)."""
    import threading
    import homebrewnlp_tpu.infer.sampler as sampler_mod

    installed = threading.Event()
    checked = threading.Event()
    other: list = []

    def worker():
        mine = lambda ev, **kw: None  # noqa: E731
        assert sampler_mod.set_decode_progress_hook(mine) is None
        installed.set()
        checked.wait(timeout=10)
        other.append(sampler_mod.decode_progress_hook() is mine)
        sampler_mod.set_decode_progress_hook(None)

    t = threading.Thread(target=worker)
    t.start()
    installed.wait(timeout=10)
    # the worker's hook is invisible here, and installing here is
    # invisible to the worker
    assert sampler_mod.decode_progress_hook() is None
    prev = sampler_mod.set_decode_progress_hook(lambda ev, **kw: 1)
    assert prev is None
    checked.set()
    t.join(timeout=10)
    sampler_mod.set_decode_progress_hook(None)
    assert other == [True]


def stepped_decode_fires_hook_test(fresh_registry):
    """The REAL stepped loop fires chunk + first_token events, and the
    instrumented decode is bit-identical to the uninstrumented one."""
    import jax.numpy as jnp
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.infer import sampler

    params = make_params(vocab_size=64, sequence_length=32, depth=2,
                         heads=2, features_per_head=8, train_batch_size=2,
                         decode_loop="stepped", decode_chunk_tokens=4)
    model = Model(params)
    rng = np.random.default_rng(0)
    tok = rng.integers(0, 64, (2, 32, 1)).astype(np.int32)
    variables = {k: jnp.asarray(v) for k, v in model.init(
        {"token_x": jnp.asarray(tok), "token_y": jnp.asarray(tok)}).items()}
    events = []
    prev = sampler.set_decode_progress_hook(
        lambda ev, **kw: events.append((ev, kw)))
    try:
        out = sampler.sample_text(model, variables, tok[:, :8, 0],
                                  initial_pos=8, temperature=0.0,
                                  end_iterations=20, seed=0)
    finally:
        sampler.set_decode_progress_hook(prev)
    kinds = [e[0] for e in events]
    assert "first_token" in kinds and kinds.count("chunk") >= 2
    chunks = [kw for ev, kw in events if ev == "chunk"]
    assert all(kw["cache_bytes"] > 0 and kw["dt"] > 0 for kw in chunks)
    assert sum(kw["steps"] for kw in chunks) == 19 - 7  # q walks 7 -> 19
    out2 = sampler.sample_text(model, variables, tok[:, :8, 0],
                               initial_pos=8, temperature=0.0,
                               end_iterations=20, seed=0)
    assert np.array_equal(out, out2), "hook changed decode output"


@pytest.mark.serving
def serving_metrics_carry_ttft_and_build_info_test():
    """Through the REAL isolated serving stack (spawn child + Manager IPC):
    a decode that reports progress lands TTFT/ITL histograms on the scraped
    /metrics, alongside the build-info gauge — the device loop installs the
    hook around the batch decode, publishes its registry over the
    heartbeat, and the HTTP child merges it at scrape time."""
    import urllib.request
    from serving_robustness_test import (_StubInterface, _post,
                                         _serve_params, _spawn_serve)
    from telemetry_test import _parse_exposition
    import homebrewnlp_tpu.infer.sampler as sampler_mod

    class _ProgressStub(_StubInterface):
        def _fire(self):
            hook = sampler_mod.decode_progress_hook()
            assert hook is not None, \
                "device loop must install the decode-progress hook"
            hook("chunk", dt=0.05, steps=5, cache_bytes=1 << 20)
            hook("first_token")

        def complete_tokens(self, *a, **k):
            self._fire()
            return super().complete_tokens(*a, **k)

        def complete_tokens_batch(self, *a, **k):
            self._fire()
            return super().complete_tokens_batch(*a, **k)

    params = _serve_params(serve_batch_size=4)
    port, stop, t = _spawn_serve(_ProgressStub(params))

    def scrape():
        req = urllib.request.Request(f"http://127.0.0.1:{port}/metrics")
        with urllib.request.urlopen(req, timeout=10) as resp:
            return resp.read().decode()

    try:
        _post(port, "/health", {})
        status, out, _ = _post(port, "/token_completion", {"tokens": [1, 2]})
        assert status == 200
        deadline = time.monotonic() + 10
        while True:
            types, series = _parse_exposition(scrape())
            if series.get(("hbnlp_serve_ttft_seconds_count", "")):
                break
            assert time.monotonic() < deadline, \
                "TTFT histogram never reached /metrics"
            time.sleep(0.1)
        assert types["hbnlp_serve_ttft_seconds"] == "histogram"
        assert series[("hbnlp_serve_itl_seconds_count", "")] >= 1
        assert types["hbnlp_build_info"] == "gauge"
        build = [k for k in series
                 if k[0] == "hbnlp_build_info" and 'git_rev="' in k[1]]
        assert build and series[build[0]] == 1
    finally:
        stop.set()
        t.join(timeout=15)
    assert not t.is_alive()


# --------------------------------------------- expensive: real audit model

@pytest.fixture(scope="module")
def audit_rig():
    from homebrewnlp_tpu.analysis import entry_points
    params, model, variables, token_x, batch = \
        entry_points.build_audit_model()
    trainer, state = entry_points.make_trainer(params, model, batch)
    hlo, ctx = entry_points.lower_train_step(params, model, variables,
                                             batch, trainer=trainer,
                                             state=state)
    return {"params": params, "model": model, "variables": variables,
            "batch": batch, "trainer": trainer, "state": state,
            "train_hlo": hlo, "train_ctx": ctx}


def committed_ledger_matches_fresh_build_test(audit_rig):
    """The regression check graft_lint --hlo runs: a fresh analytical build
    of the train-step entry agrees with analysis/cost_ledger.json within
    tolerance (full four-entry agreement is checked by the lint itself)."""
    stored = cost_ledger.load_ledger()
    fresh = cost_ledger.scope_table(audit_rig["train_ctx"]["trace"]())
    old = stored["entry_points"]["train_step"]
    tol = stored["tolerance"]
    assert set(fresh["scopes"]) == set(old["scopes"])
    for scope, s in fresh["scopes"].items():
        for metric in ("flops", "bytes"):
            a, b = old["scopes"][scope][metric], s[metric]
            assert abs(b - a) <= tol * max(abs(a), 1), (scope, metric, a, b)
