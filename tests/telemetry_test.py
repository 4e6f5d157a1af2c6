"""Telemetry subsystem (marker: telemetry; docs/OBSERVABILITY.md).

Unit sweep: registry semantics, Prometheus text-exposition conformance
(rendered text is parsed BACK and checked against the snapshot), histogram
bucket accounting under concurrent writers, snapshot merge/JSONL/summary
renderers, span -> histogram + profiler annotation, the on-demand profiler
state machine, the MetricLogger monotonic-clock fix, and the per-layer wiring
(prefetcher, retry sites, checkpoint IO, set-up spans, compile counters).

Integration sweep: a train smoke run whose per-step spans reach the registry
only under ``telemetry_enabled`` (and cost no device sync either way), a
``profile_steps`` capture holding the program's spans on the profiler's
clock, SIGUSR2-triggered profile capture, and —
device-free, on the serving_robustness_test harness — ``GET /metrics``
answering valid exposition from the HTTP child while the device loop is
wedged inside a decode."""
import json
import os
import re
import signal
import threading
import time
import urllib.request

import numpy as np
import pytest

from homebrewnlp_tpu import telemetry
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import declare

pytestmark = pytest.mark.telemetry


@pytest.fixture
def fresh_registry():
    prev = telemetry.set_registry(telemetry.Registry())
    yield telemetry.registry()
    telemetry.set_registry(prev)


# ---------------------------------------------------------------- unit sweep

def registry_basics_test():
    r = telemetry.Registry()
    c = r.counter("c_total", "a counter", ("site",))
    c.labels(site="gcs").inc()
    c.labels("gcs").inc(2.5)        # positional and kwargs name the same series
    with pytest.raises(ValueError):
        c.labels(site="gcs").inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        c.inc()                       # labelled metric needs labels()
    g = r.gauge("g")
    g.set(3)
    g.set(1.5)
    h = r.histogram("h_seconds", buckets=(1.0, 2.0))
    h.observe(0.5)
    h.observe(1.0)   # le is INCLUSIVE: lands in the 1.0 bucket
    h.observe(99.0)  # +Inf bucket
    with pytest.raises(TypeError):
        g.observe(1.0)
    with pytest.raises(ValueError):
        r.counter("g")  # kind mismatch on re-registration
    snap = r.snapshot()
    assert snap["c_total"]["series"][("gcs",)] == 3.5
    assert snap["g"]["series"][()] == 1.5
    assert snap["h_seconds"]["series"][()]["counts"] == [2, 0, 1]
    assert snap["h_seconds"]["series"][()]["sum"] == pytest.approx(100.5)
    # same name + kind returns the same metric (idempotent registration)
    assert r.counter("c_total", labelnames=("site",)) is c


def _parse_exposition(text: str):
    """Minimal conformance parser for the text format: returns
    ({name: kind}, {(name, labelstring): value}) and asserts line shape."""
    types, series = {}, {}
    for line in text.strip().split("\n"):
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert kind in ("counter", "gauge", "histogram")
            types[name] = kind
        elif line.startswith("#"):
            assert line.startswith("# HELP "), line
        else:
            m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
                         r"(?:\{(.*)\})? (\S+)$", line)
            assert m, f"malformed sample line: {line!r}"
            name, labels, value = m.groups()
            series[(name, labels or "")] = float(value)
    return types, series


def prometheus_exposition_conformance_test():
    """Render -> parse back -> the parsed samples match the snapshot:
    counter/gauge values, INCLUSIVE cumulative histogram buckets, +Inf
    bucket == _count, _sum, and label-value escaping."""
    r = telemetry.Registry()
    r.counter("req_total", "requests", ("path", "code")) \
        .labels(path="/x", code="200").inc(7)
    r.gauge("depth", "queue depth").set(4)
    weird = 'a"b\\c\nd'
    r.counter("esc_total", "escaping", ("v",)).labels(v=weird).inc()
    h = r.histogram("lat_seconds", "latency", ("op",), buckets=(0.1, 1, 10))
    for v in (0.05, 0.1, 0.5, 3.0, 99.0):
        h.labels(op="read").observe(v)
    text = telemetry.prometheus_text(r.snapshot())
    types, series = _parse_exposition(text)
    assert types == {"req_total": "counter", "depth": "gauge",
                     "esc_total": "counter", "lat_seconds": "histogram"}
    assert series[("req_total", 'path="/x",code="200"')] == 7
    assert series[("depth", "")] == 4
    # escaped label value appears exactly per the format rules
    assert ('esc_total', 'v="a\\"b\\\\c\\nd"') in series
    # cumulative buckets: 0.1 is inclusive (2 of 0.05,0.1), then 3 <= 1, etc.
    assert series[("lat_seconds_bucket", 'op="read",le="0.1"')] == 2
    assert series[("lat_seconds_bucket", 'op="read",le="1"')] == 3
    assert series[("lat_seconds_bucket", 'op="read",le="10"')] == 4
    assert series[("lat_seconds_bucket", 'op="read",le="+Inf"')] == 5
    assert series[("lat_seconds_count", 'op="read"')] == 5
    assert series[("lat_seconds_sum", 'op="read"')] == pytest.approx(102.65)
    cum = [series[("lat_seconds_bucket", f'op="read",le="{b}"')]
           for b in ("0.1", "1", "10", "+Inf")]
    assert cum == sorted(cum), "bucket counts must be cumulative-monotone"


def histogram_concurrent_writers_test():
    """Bucket accounting stays exact under concurrent writers: total count,
    per-bucket sums, and the sum of observations all reconcile."""
    r = telemetry.Registry()
    h = r.histogram("conc_seconds", buckets=(0.25, 0.5, 0.75))
    c = r.counter("conc_total")
    threads, per_thread = 8, 2000
    values = [i / per_thread for i in range(per_thread)]  # 0 .. 0.9995

    def work():
        child = r.histogram("conc_seconds").labels()
        for v in values:
            child.observe(v)
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = r.snapshot()
    state = snap["conc_seconds"]["series"][()]
    n = threads * per_thread
    assert sum(state["counts"]) == n
    assert snap["conc_total"]["series"][()] == n
    # each quarter-bucket holds exactly threads * per_thread/4 observations
    # (le inclusive: 0.25 itself lands in the first bucket)
    expect = threads * (per_thread // 4)
    assert state["counts"] == [expect + threads, expect, expect,
                               expect - threads]
    assert state["sum"] == pytest.approx(threads * sum(values))


def merge_and_render_test():
    ra, rb = telemetry.Registry(), telemetry.Registry()
    ra.counter("n_total").inc(2)
    rb.counter("n_total").inc(3)
    ra.gauge("g").set(1)
    rb.gauge("g").set(9)
    ha = ra.histogram("h", buckets=(1,))
    hb = rb.histogram("h", buckets=(1,))
    ha.observe(0.5)
    hb.observe(2.0)
    merged = telemetry.merge_snapshots(ra.snapshot(), rb.snapshot())
    assert merged["n_total"]["series"][()] == 5     # counters sum
    assert merged["g"]["series"][()] == 9           # gauges: later wins
    assert merged["h"]["series"][()]["counts"] == [1, 1]
    assert merged["h"]["series"][()]["sum"] == 2.5
    # JSONL line round-trips through json with flat series keys
    line = telemetry.jsonl_line(merged, step=7)
    doc = json.loads(line)
    assert doc["step"] == 7
    assert doc["metrics"]["n_total"]["series"][""] == 5
    assert doc["metrics"]["h"]["series"][""]["count"] == 2


def merged_histogram_inf_cumulativity_test():
    """Exposition of a MERGED snapshot stays conformant: bucket counts are
    cumulative-monotone and the +Inf bucket equals _count equals the total
    observation count across both source processes (the /metrics scrape
    path renders merge_snapshots output, so the invariant must survive the
    merge, not just a single registry)."""
    ra, rb = telemetry.Registry(), telemetry.Registry()
    ha = ra.histogram("m_seconds", "merged", ("op",), buckets=(0.1, 1, 10))
    hb = rb.histogram("m_seconds", "merged", ("op",), buckets=(0.1, 1, 10))
    for v in (0.05, 0.5, 99.0):
        ha.labels(op="w").observe(v)
    for v in (0.1, 3.0, 50.0, 7.0):       # 0.1 inclusive in first bucket
        hb.labels(op="w").observe(v)
    # the multi-snapshot prometheus_text path merges internally
    types, series = _parse_exposition(
        telemetry.prometheus_text(ra.snapshot(), rb.snapshot()))
    assert types["m_seconds"] == "histogram"
    cum = [series[("m_seconds_bucket", f'op="w",le="{b}"')]
           for b in ("0.1", "1", "10", "+Inf")]
    assert cum == [2, 3, 5, 7]            # monotone, both processes summed
    assert series[("m_seconds_bucket", 'op="w",le="+Inf"')] \
        == series[("m_seconds_count", 'op="w"')] == 7
    assert series[("m_seconds_sum", 'op="w"')] == pytest.approx(159.65)


def merge_bucket_mismatch_rejected_test():
    """Snapshots whose histograms disagree on bucket boundaries refuse to
    merge (a silent zip() would drop counts from the longer list)."""
    ra, rb = telemetry.Registry(), telemetry.Registry()
    ra.histogram("mm_seconds", buckets=(1.0, 2.0)).observe(0.5)
    rb.histogram("mm_seconds", buckets=(1.0, 2.0, 4.0)).observe(0.5)
    with pytest.raises(ValueError, match="mm_seconds.*bucket"):
        telemetry.merge_snapshots(ra.snapshot(), rb.snapshot())


def help_and_label_escaping_test():
    """Format 0.0.4 has TWO escaping rules: HELP text escapes only
    backslash and line feed (a double quote passes through verbatim);
    label values additionally escape the double quote."""
    r = telemetry.Registry()
    weird = 'say "hi"\\\n done'
    r.counter("esc2_total", weird, ("v",)).labels(v=weird).inc()
    text = telemetry.prometheus_text(r.snapshot())
    help_line = [ln for ln in text.splitlines()
                 if ln.startswith("# HELP esc2_total ")][0]
    assert help_line == '# HELP esc2_total say "hi"\\\\\\n done'
    sample = [ln for ln in text.splitlines()
              if ln.startswith("esc2_total{")][0]
    assert sample == 'esc2_total{v="say \\"hi\\"\\\\\\n done"} 1'
    # exposition stays one-line-per-sample: no raw newline leaked
    assert all("\n" not in ln for ln in (help_line, sample))


def gauge_last_wins_interleaved_test():
    """Gauge merge semantics under interleaved publishes from two
    processes: the LAST snapshot argument wins per series — even when its
    value is 0/falsy — series absent from later snapshots survive from
    earlier ones, and counters keep summing regardless of order."""
    dev, child = telemetry.Registry(), telemetry.Registry()
    g_dev = dev.gauge("depth", "queue depth", ("q",))
    g_child = child.gauge("depth", "queue depth", ("q",))
    c_dev, c_child = dev.counter("n_total"), child.counter("n_total")
    g_dev.labels(q="a").set(5)
    g_dev.labels(q="b").set(7)          # only the device loop publishes b
    c_dev.inc(2)
    snap_dev1 = dev.snapshot()
    g_child.labels(q="a").set(3)
    c_child.inc(1)
    snap_child = child.snapshot()
    g_dev.labels(q="a").set(0)          # falsy newest value must still win
    c_dev.inc(4)
    snap_dev2 = dev.snapshot()

    # scrape 1 lands between the two device publishes: child passed last
    m1 = telemetry.merge_snapshots(snap_dev1, snap_child)
    assert m1["depth"]["series"][("a",)] == 3       # later argument wins
    assert m1["depth"]["series"][("b",)] == 7       # absent later: survives
    assert m1["n_total"]["series"][()] == 3         # counters sum
    # scrape 2 sees the fresher device publish last: its 0 must still win
    m2 = telemetry.merge_snapshots(snap_child, snap_dev2)
    assert m2["depth"]["series"][("a",)] == 0
    assert m2["depth"]["series"][("b",)] == 7
    assert m2["n_total"]["series"][()] == 7
    # argument order IS the tiebreak: same snapshots, flipped, flip the gauge
    assert telemetry.merge_snapshots(
        snap_dev2, snap_child)["depth"]["series"][("a",)] == 3


def _host_spans(logdir):
    """``{name: [(start_ns, end_ns)]}`` of the ``python`` line of the
    newest capture under ``logdir`` (the line ``TraceAnnotation`` spans of
    the main thread land on), and how many captures there are."""
    import glob
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(
        str(logdir), "plugins", "profile", "*", "*.xplane.pb")))
    assert found, f"no capture under {logdir}"
    out = {}
    for plane in ProfileData.from_file(found[-1]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name != "python":
                continue
            for e in line.events:
                out.setdefault(e.name, []).append(
                    (e.start_ns, e.start_ns + e.duration_ns))
    return out, len(found)


def span_records_and_annotates_test(monkeypatch):
    """A span observes the histogram when it records and never when it
    does not; ``Phase.rec`` serves callers that own the clock; a process
    that has not imported jax gets no annotation and no import."""
    import sys
    from homebrewnlp_tpu.telemetry import spans
    r = telemetry.Registry()
    clock = iter([1.0, 1.25]).__next__
    with telemetry.span("ckpt/save", r, clock=clock):
        pass
    snap = r.snapshot()
    state = snap[telemetry.SPAN_METRIC]["series"][("ckpt/save",)]
    assert sum(state["counts"]) == 1 and state["sum"] == pytest.approx(0.25)
    # a per-step site with telemetry off: annotation only, no clock read,
    # no registry call
    def no_clock():
        raise AssertionError("a span that does not record read the clock")
    with telemetry.span("train/step_dispatch", r, clock=no_clock,
                        record=False):
        pass
    assert ("train/step_dispatch",) not in \
        r.snapshot()[telemetry.SPAN_METRIC]["series"]
    telemetry.Phase("bench/device_block", r).rec(9.0, 0.125)
    assert snap is not r.snapshot()  # snapshot is a copy, not a live view
    got = r.snapshot()[telemetry.SPAN_METRIC]["series"]
    assert got[("bench/device_block",)]["sum"] == pytest.approx(0.125)
    # stdlib-only consumers (the HTTP child): jax is looked up, not imported
    assert spans._annotation("x") is not None     # jax is loaded here
    monkeypatch.delitem(sys.modules, "jax")
    assert spans._annotation("x") is None
    with telemetry.span("child/handler", r):
        pass
    assert "jax" not in sys.modules
    assert ("child/handler",) in r.snapshot()[telemetry.SPAN_METRIC]["series"]


def span_under_capture_lands_in_histogram_and_trace_test(tmp_path):
    """One clock: the same ``with telemetry.span(...)`` is an observation
    in the registry and an event of the profiler's trace, nested in time
    under the span that encloses it."""
    r = telemetry.Registry()
    telemetry.start_capture(str(tmp_path))
    try:
        with telemetry.span("unit/outer", r):
            with telemetry.span("unit/inner", r):
                time.sleep(0.01)
    finally:
        import jax
        jax.profiler.stop_trace()
    series = r.snapshot()[telemetry.SPAN_METRIC]["series"]
    assert sum(series[("unit/inner",)]["counts"]) == 1
    assert series[("unit/inner",)]["sum"] >= 0.01
    spans, _ = _host_spans(tmp_path)
    (o0, o1), = spans["unit/outer"]
    (i0, i1), = spans["unit/inner"]
    assert o0 <= i0 and i1 <= o1
    # both clocks saw the same block: within a millisecond of each other
    assert (i1 - i0) * 1e-9 == pytest.approx(
        series[("unit/inner",)]["sum"], abs=1e-3)
    # the Python tracer is off: the capture is not buried in call events
    assert not [n for n in spans if n.startswith("$")]


def on_demand_profiler_test(tmp_path):
    calls = []
    p = telemetry.OnDemandProfiler(str(tmp_path), capture_steps=3,
                                   start=lambda d: calls.append(("start", d)),
                                   stop=lambda: calls.append(("stop",)))
    p.poll(0)
    assert calls == []          # nothing requested: zero cost
    p.request()
    p.poll(10)                  # starts at the next poll
    assert p.active and calls == [("start", str(tmp_path) + "/on_demand_10")]
    p.poll(11)
    p.poll(12)
    assert p.active             # 10 + 3 not reached
    p.poll(13)
    assert not p.active and calls[-1] == ("stop",)
    p.request()
    p.poll(20)
    p.request()                 # second request while active = stop early
    p.poll(21)
    assert not p.active and calls[-1] == ("stop",)
    # a failing start is reported, never fatal, and leaves it inactive
    boom = telemetry.OnDemandProfiler(
        str(tmp_path), start=lambda d: (_ for _ in ()).throw(RuntimeError()))
    boom.request()
    boom.poll(0)
    assert not boom.active


def metric_logger_monotonic_test(tmp_path):
    """steps_per_sec comes off an injectable monotonic clock: a wall-clock
    step (NTP) between logs can no longer produce negative rates."""
    from homebrewnlp_tpu.train.metrics import MetricLogger
    t = [100.0]
    logger = MetricLogger(str(tmp_path), enable_tb=False,
                          clock=lambda: t[0])
    logger.log(1, {"loss": 1.0}, tokens_per_step=10)
    t[0] += 2.0
    logger.log(3, {"loss": 0.9}, tokens_per_step=10)
    logger.flush()
    logger.close()
    logger.close()  # idempotent: the emergency path closes eagerly
    lines = [json.loads(x) for x in
             open(os.path.join(tmp_path, "metrics.jsonl"))]
    assert "steps_per_sec" not in lines[0]
    assert lines[1]["steps_per_sec"] == pytest.approx(1.0)
    assert lines[1]["tokens_per_sec"] == pytest.approx(10.0)
    assert lines[1]["wall"] == pytest.approx(2.0)


def prefetcher_telemetry_gating_test(fresh_registry):
    from homebrewnlp_tpu.data.inputs import Prefetcher
    # no label (the telemetry_enabled=false path): ZERO registry calls per
    # item — the one thing recorded is the set-up span of the first batch
    list(Prefetcher(iter(range(4)), depth=2))
    snap = fresh_registry.snapshot()
    assert set(snap) == {telemetry.SPAN_METRIC}
    first, = snap[telemetry.SPAN_METRIC]["series"].items()
    assert first[0] == ("setup/data_first_batch",)
    assert sum(first[1]["counts"]) == 1
    out = list(Prefetcher(iter(range(5)), depth=2, telemetry_label="train"))
    assert out == list(range(5))
    snap = fresh_registry.snapshot()
    assert snap["hbnlp_prefetch_items_total"]["series"][("train",)] == 5
    assert ("train",) in snap["hbnlp_prefetch_queue_depth"]["series"]
    # with the label the consumer's waits are recorded: 5 items + the end
    waits = snap[telemetry.SPAN_METRIC]["series"][("data/next",)]
    assert sum(waits["counts"]) == 6


def retry_site_counters_test(fresh_registry):
    from homebrewnlp_tpu.utils.retry import RetryPolicy, TransientError
    policy = RetryPolicy(max_attempts=3, base_delay=0.0, sleep=lambda s: None)
    boom = [0]

    def flaky():
        boom[0] += 1
        if boom[0] < 3:
            raise TransientError("blip")
        return "ok"

    assert policy.call(flaky, site="gcs") == "ok"
    with pytest.raises(FileNotFoundError):
        policy.call(lambda: (_ for _ in ()).throw(FileNotFoundError("x")),
                    site="checkpoint")
    with pytest.raises(TransientError):
        policy.call(lambda: (_ for _ in ()).throw(TransientError("down")),
                    site="gcs")
    snap = fresh_registry.snapshot()
    assert snap["hbnlp_storage_retries_total"]["series"][("gcs",)] == 4
    fails = snap["hbnlp_storage_failures_total"]["series"]
    assert fails[("checkpoint", "permanent")] == 1
    assert fails[("gcs", "exhausted")] == 1


def checkpoint_io_metrics_test(tmp_path, fresh_registry, monkeypatch):
    """Checkpoint saves/restores record bytes, durations, and crc failures
    into the registry (always on — checkpoint cadence, not the hot path)."""
    from homebrewnlp_tpu.train import checkpoint as ckpt
    monkeypatch.setattr(ckpt, "_metrics_cache", None)  # rebind to fresh reg
    variables = {"w": np.arange(8, dtype=np.float32)}
    opt = {"m": {"w": np.zeros(8, np.float32)}}
    d = str(tmp_path / "run")
    ckpt.save(d, 3, variables, opt, max_keep=2)
    restored = ckpt.restore(d)
    assert restored is not None and restored[2] == 3
    snap = fresh_registry.snapshot()
    per_op = snap["hbnlp_checkpoint_bytes_total"]["series"]
    assert per_op[("write",)] >= 64 and per_op[("read",)] >= 64
    secs = snap["hbnlp_checkpoint_seconds"]["series"]
    assert sum(secs[("save",)]["counts"]) == 1
    assert sum(secs[("restore",)]["counts"]) == 1
    # flip one payload byte -> crc failure counter + CheckpointError
    target = os.path.join(d, "ckpt_3", "arr_000000.bin")
    blob = bytearray(open(target, "rb").read())
    blob[0] ^= 0xFF
    open(target, "wb").write(bytes(blob))
    with pytest.raises(ckpt.CheckpointError, match="verification"):
        ckpt.restore(d)
    snap = fresh_registry.snapshot()
    assert snap["hbnlp_checkpoint_crc_failures_total"]["series"][()] == 1


# -------------------------------------------------------- integration sweep

#: what a run records whatever ``telemetry_enabled`` says: once a run or at
#: log / checkpoint / compile cadence, never per step
_RARE_SERIES = {telemetry.SPAN_METRIC, "hbnlp_init_values_seconds_total",
                "hbnlp_init_values_total",
                "hbnlp_init_values_cpu_seconds_total", "hbnlp_init_workers",
                "hbnlp_compile_seconds_total", "hbnlp_compiles_total",
                # set once, when the step is built (PR 27)
                "hbnlp_remat_stash_bytes", "hbnlp_remat_stash_layers",
                # likewise: the start-up facts the layers declare that read
                # 0 without their layer (model/declare.py Fact)
                *(fact.metric for fact in declare.facts() if fact.zero),
                # set at the marks of telemetry/memory.py, where the backend
                # reports memory (PR 34; XLA:CPU: no series)
                "hbnlp_hbm_bytes", "hbnlp_train_state_bytes",
                # a stalled step, when the step clock names one (PR 51):
                # rare by its rule, so unconditional; nothing per step
                "hbnlp_step_stalls_total", "hbnlp_step_stall_seconds_total"}
_RARE_SPANS = {"setup/data_first_batch", "setup/model_init",
               "setup/place_params", "setup/opt_init", "setup/init_wait",
               "train/metric_log", "train/checkpoint_save", "train/eval",
               # once each; memory/running only under telemetry_enabled
               "memory/params_placed", "memory/state_ready",
               "memory/step_loaded"}
_STEP_SPANS = ("train/step_dispatch", "data/next", "data/place")


def train_step_phase_breakdown_test(tmp_path, fresh_registry):
    """Tentpole acceptance: with telemetry off a train smoke run records
    its set-up and rare spans and NOTHING per step — the flight recorder
    included, which keeps recording at rare-event cadence only (step
    records at the log cadence, never per step, never into the registry);
    with it on, the per-step spans placed where the work happens
    (``Trainer.step``, ``place_batch``, the prefetcher) reach the histogram,
    with the prefetcher series, the token counter and a telemetry.jsonl
    trajectory — and no second exporter, no utilization gauge."""
    from robustness_test import _train_cfg, _write_records
    from homebrewnlp_tpu.run import train_loop as tl
    from homebrewnlp_tpu.telemetry import events as flight

    data_dir = _write_records(tmp_path)
    cfg = _train_cfg(tmp_path, data_dir, use_checkpointing=False)
    prev_rec = flight.set_recorder()
    try:
        result = tl.train(ModelParameter(cfg), log_every=2)
        assert result["final_step"] == cfg["train_steps"]
        snap = fresh_registry.snapshot()
        assert set(snap) <= _RARE_SERIES, set(snap) - _RARE_SERIES
        recorded = {k[0] for k in snap[telemetry.SPAN_METRIC]["series"]}
        assert recorded <= _RARE_SPANS, recorded - _RARE_SPANS
        assert {"setup/model_init", "train/metric_log"} <= recorded
        # the metric log is the loop's one sync: log cadence, not per step
        logs = snap[telemetry.SPAN_METRIC]["series"][("train/metric_log",)]
        assert sum(logs["counts"]) == cfg["train_steps"] // 2
        # the flight recorder recorded UNCONDITIONALLY — but at rare-event
        # cadence: step events ride the log cadence, not the hot path
        rec = flight.recorder()
        kinds = {e["kind"] for e in rec.events()}
        assert {"run_start", "exit"} <= kinds, kinds
        steps = [e for e in rec.events() if e["kind"] == "step"]
        assert 0 < len(steps) <= cfg["train_steps"] // 2 + 1, len(steps)
        assert steps[-1]["loss"] is not None
        # ... and the blackbox dump landed on the normal exit path
        bb = os.path.join(cfg["model_path"], "blackbox_p0.jsonl")
        lines = [json.loads(x) for x in open(bb)]
        assert lines[0]["blackbox"]["tag"] == "p0"
        exits = [x for x in lines if x.get("kind") == "exit"]
        assert exits and exits[-1]["reason"] == "ok"
    finally:
        flight.set_recorder(prev_rec)

    cfg = _train_cfg(tmp_path, data_dir, use_checkpointing=False,
                     model_path=str(tmp_path / "run2"),
                     telemetry_enabled=True,
                     telemetry_jsonl_interval_s=1e-6)
    result = tl.train(ModelParameter(cfg), log_every=2)
    assert result["final_step"] == cfg["train_steps"]
    snap = fresh_registry.snapshot()
    spans = snap[telemetry.SPAN_METRIC]["series"]
    steps = cfg["train_steps"]
    for phase in _STEP_SPANS:
        state = spans[(phase,)]
        # first_batch is fetched before the loop and handed to the first
        # step unplaced: the loop itself sees steps - 1 of the data spans
        assert sum(state["counts"]) >= steps - 1, phase
        assert state["sum"] >= 0
    assert sum(spans[("train/step_dispatch",)]["counts"]) == steps
    assert snap["hbnlp_prefetch_items_total"]["series"][("train",)] >= steps
    # token throughput: every consumed token counted (a rate over it is the
    # operator's tokens/s); the build-info gauge identifies the run; the
    # per-step MFU gauge is gone with the sync it needed
    assert "hbnlp_train_mfu" not in snap
    tokens_per_step = (cfg["train_batch_size"] * cfg["sequence_length"]
                       * max(1, cfg.get("macro_batching", 1)))
    assert snap["hbnlp_train_tokens_total"]["series"][()] \
        == steps * tokens_per_step
    build_series = snap["hbnlp_build_info"]["series"]
    assert len(build_series) == 1 and list(build_series.values()) == [1]
    # the JSONL trajectory parses and carries the span series; its header
    # line joins the file to the build that wrote it
    jsonl = os.path.join(cfg["model_path"], "telemetry.jsonl")
    lines = [json.loads(x) for x in open(jsonl)]
    assert set(lines[0]["build_info"]) == {"git_rev", "jax_version",
                                           "backend", "device_kind"}
    assert lines and telemetry.SPAN_METRIC in lines[-1]["metrics"]
    assert lines[-1]["step"] == steps
    # one exporter: the spans are in the XLA profile, not in a file of
    # their own
    assert not os.path.exists(os.path.join(cfg["model_path"],
                                           "telemetry_trace.json"))


def train_profile_capture_holds_program_spans_test(tmp_path, fresh_registry):
    """``train(profile_steps=...)`` on the CPU: the capture's ``python``
    line holds the program's own spans — one ``train/step_dispatch``,
    ``data/next`` and ``data/place`` per captured step, in loop order, and
    the metric log's sync — so a host span can be laid against a device
    gap.  One window gives one capture (it used to start a second one on
    the turn after it stopped)."""
    from robustness_test import _train_cfg, _write_records
    from homebrewnlp_tpu.run import train_loop as tl

    cfg = _train_cfg(tmp_path, _write_records(tmp_path),
                     use_checkpointing=False, async_input_transfer=True)
    tl.train(ModelParameter(cfg), log_every=2, profile_steps=(2, 6))
    spans, captures = _host_spans(os.path.join(cfg["model_path"], "profile"))
    assert captures == 1
    for name in _STEP_SPANS:
        assert len(spans[name]) == 4, (name, len(spans.get(name, ())))
    assert len(spans["train/metric_log"]) == 2
    # by time on one thread: dispatch, then the wait on the queue, then the
    # placement of the batch after next, step after step, none overlapping
    order = sorted((iv, n) for n in _STEP_SPANS for iv in spans[n])
    assert [n for _, n in order] == list(_STEP_SPANS) * 4
    assert all(a[0][1] <= b[0][0] for a, b in zip(order, order[1:]))
    # telemetry is off: the capture cost the registry nothing per step
    recorded = {k[0] for k in fresh_registry.snapshot()
                [telemetry.SPAN_METRIC]["series"]}
    assert not recorded & set(_STEP_SPANS)


def _toy_trainer(tmp_path, **overrides):
    from robustness_test import _train_cfg, _write_records
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.train import Trainer
    params = ModelParameter(_train_cfg(tmp_path, _write_records(tmp_path),
                                       use_checkpointing=False, **overrides))
    rng = np.random.default_rng(0)

    def batch(dtype=np.int32):
        x = rng.integers(0, 32, (8, 16, 1)).astype(dtype)
        return {"token_x": x, "token_y": x}

    return Trainer(params, Model(params)), batch


def setup_spans_and_init_value_count_test(tmp_path, fresh_registry):
    """``Trainer.init_state`` splits itself: the model's graph walk, the
    wait for the values (counted one per parameter made), placement,
    optimizer slots, and the one wait that closes it."""
    trainer, batch = _toy_trainer(tmp_path)
    state = trainer.init_state(batch())
    snap = fresh_registry.snapshot()
    spans = snap[telemetry.SPAN_METRIC]["series"]
    for name in ("setup/model_init", "setup/place_params", "setup/opt_init",
                 "setup/init_wait"):
        assert sum(spans[(name,)]["counts"]) == 1, name
    made = snap["hbnlp_init_values_total"]["series"][()]
    assert made == len(state.variables) > 0
    in_values = snap["hbnlp_init_values_seconds_total"]["series"][()]
    assert 0 <= in_values <= spans[("setup/model_init",)]["sum"]
    # a second init makes the values again and counts them again
    trainer.init_state(batch())
    assert fresh_registry.snapshot()["hbnlp_init_values_total"] \
        ["series"][()] == 2 * made


@pytest.mark.parametrize("cores", [1, 4])
def init_counter_contract_test(tmp_path, fresh_registry, monkeypatch, cores):
    """After one ``Model.init``: a value counted per parameter; the wall
    seconds waited for values never more than the ``setup/model_init`` span
    (the benchmark's ``init_trace_s`` = span - counter stays the walk, >= 0);
    CPU seconds across the workers; the pool's width, 1 on one core."""
    from homebrewnlp_tpu.core import value_pool
    monkeypatch.setattr(value_pool, "usable_cores", lambda: cores)
    trainer, batch = _toy_trainer(tmp_path)
    variables = trainer.model.init(batch())
    snap = fresh_registry.snapshot()

    def series(name):
        return snap[name]["series"][()]

    span = snap[telemetry.SPAN_METRIC]["series"][("setup/model_init",)]
    assert sum(span["counts"]) == 1
    assert series("hbnlp_init_values_total") == len(variables) > 0
    assert 0 <= series("hbnlp_init_values_seconds_total") <= span["sum"]
    assert series("hbnlp_init_values_cpu_seconds_total") > 0
    assert series("hbnlp_init_workers") == min(cores, len(variables)) >= 1
    assert snap["hbnlp_init_workers"]["kind"] == "gauge"


def compile_counter_counts_recompiles_test(tmp_path, fresh_registry):
    """``hbnlp_compiles_total{phase="backend", fun="step_fn"}`` answers
    "which step recompiled": a batch of another signature raises it by one,
    an unchanged one by none.  (The toy model pins the batch's shape to its
    dims, so the changed signature is the tokens' dtype.)"""
    telemetry.install_compile_listener()
    telemetry.install_compile_listener()        # idempotent: no double count
    trainer, batch = _toy_trainer(tmp_path)
    state = trainer.init_state(batch())

    def compiles(phase="backend"):
        series = fresh_registry.snapshot().get(
            "hbnlp_compiles_total", {}).get("series", {})
        return series.get((phase, "step_fn"), 0)

    assert compiles() == 0
    state, _ = trainer.step(state, batch())
    assert compiles() == 1 and compiles("lower") == 1
    state, _ = trainer.step(state, batch())
    assert compiles() == 1
    state, _ = trainer.step(state, batch(np.uint8))
    assert compiles() == 2
    state, _ = trainer.step(state, batch(np.uint8))
    assert compiles() == 2
    seconds = fresh_registry.snapshot()["hbnlp_compile_seconds_total"]
    assert seconds["series"][("backend", "step_fn")] > 0
    assert seconds["series"][("trace", "step_fn")] > 0


def telemetry_enabled_adds_no_per_step_sync_test(tmp_path, fresh_registry,
                                                  monkeypatch):
    """Turning the measurement on does not change what is measured:
    ``train()`` under ``telemetry_enabled`` calls ``block_until_ready``
    once (the end of ``init_state``), whatever the number of steps."""
    import jax
    from robustness_test import _train_cfg, _write_records
    from homebrewnlp_tpu.run import train_loop as tl

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    data_dir = _write_records(tmp_path)
    for steps in (4, 8):
        del calls[:]
        cfg = _train_cfg(tmp_path, data_dir, use_checkpointing=False,
                         model_path=str(tmp_path / f"run{steps}"),
                         train_steps=steps, telemetry_enabled=True)
        tl.train(ModelParameter(cfg), log_every=100)
        assert len(calls) == 1, (steps, len(calls))
    spans = fresh_registry.snapshot()[telemetry.SPAN_METRIC]["series"]
    assert sum(spans[("train/step_dispatch",)]["counts"]) == 12


class _CountingRegistry(telemetry.Registry):
    """Counts every metric lookup: each registry call on any path goes
    through ``counter`` / ``gauge`` / ``histogram``, or through a child
    bound by one of them."""

    def __init__(self):
        super().__init__()
        self.lookups = 0

    def _get_or_create(self, *args, **kwargs):
        self.lookups += 1
        return super()._get_or_create(*args, **kwargs)


def telemetry_off_step_makes_no_registry_call_test(tmp_path, monkeypatch):
    """With ``telemetry_enabled`` false a steady-state step — queue wait,
    placement, dispatch — makes no registry call; with it true the same
    three sites each make one (the control that the counter can see them).
    Steady state begins once ``step_loaded`` is marked (telemetry/memory.py:
    the devices report memory here, so that mark does set its gauges): the
    step after it pays one ``not``.  The step clock runs either way: its
    ring holds every one of these steps, dispatch, placement and queue wait
    timed, at no registry call (with telemetry on its intervals reach
    ``hbnlp_step_seconds`` through a child bound at the first)."""
    import jax
    from memory_marks_test import _report_memory
    from homebrewnlp_tpu.data.inputs import Prefetcher
    from homebrewnlp_tpu.telemetry import memory
    _report_memory(monkeypatch)
    for enabled, expected in ((False, 0), (True, 9)):
        counting = _CountingRegistry()
        prev = telemetry.set_registry(counting)
        try:
            trainer, batch = _toy_trainer(tmp_path,
                                          telemetry_enabled=enabled)
            state = trainer.init_state(batch())
            feed = Prefetcher((batch() for _ in range(8)), depth=2,
                              telemetry_label="t" if enabled else None)
            try:
                for _ in range(2):      # warm-up: the compile records
                    state, metrics = trainer.step(
                        state, trainer.place_batch(next(feed)))
                    jax.block_until_ready(metrics["loss"])
                assert trainer._step_loaded
                before = counting.lookups
                snap = counting.snapshot()
                assert ("step_loaded", "in_use") in \
                    snap[memory.HBM_METRIC]["series"]
                for _ in range(3):
                    state, _ = trainer.step(
                        state, trainer.place_batch(next(feed)))
                assert counting.lookups - before == expected, enabled
                if not enabled:
                    assert counting.snapshot() == snap
                ring = list(trainer.step_clock.ring)
                assert [e.index for e in ring] == list(range(5))
                for e in ring[2:]:
                    assert e.exit_ns > e.enter_ns and e.cpu_ns > 0
                for e in ring[1:4]:
                    # the turn a step opens holds the fetch and placement
                    # of the batch for the next
                    assert e.data_place_ns > 0 and e.data_next_ns > 0
                assert trainer.step_clock.completed >= 2
                steps = counting.snapshot().get("hbnlp_step_seconds")
                assert (steps is not None) == enabled
            finally:
                feed.close()
        finally:
            telemetry.set_registry(prev)


def sigusr2_profile_capture_test(tmp_path, fresh_registry, monkeypatch):
    """telemetry_profile_on_signal: SIGUSR2 mid-run starts a jax.profiler
    capture at the next loop tick and stops it telemetry_profile_steps
    steps later, under <model_path>/profile/on_demand_<step>."""
    import jax
    from robustness_test import _train_cfg, _write_records
    import homebrewnlp_tpu.train.metrics as metrics_mod
    from homebrewnlp_tpu.run import train_loop as tl

    captures = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **k: captures.append(["start", d]))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: captures.append(["stop"]))
    orig_log = metrics_mod.MetricLogger.log
    fired = []

    def log_then_signal(self, step, *a, **k):
        orig_log(self, step, *a, **k)
        if step >= 2 and not fired:
            fired.append(step)
            signal.raise_signal(signal.SIGUSR2)

    monkeypatch.setattr(metrics_mod.MetricLogger, "log", log_then_signal)
    cfg = _train_cfg(tmp_path, _write_records(tmp_path),
                     use_checkpointing=False,
                     telemetry_profile_on_signal=True,
                     telemetry_profile_steps=2)
    result = tl.train(ModelParameter(cfg), log_every=1)
    assert result["final_step"] == cfg["train_steps"]
    assert ["stop"] in captures
    starts = [c for c in captures if c[0] == "start"]
    assert len(starts) == 1
    assert starts[0][1].startswith(os.path.join(cfg["model_path"],
                                                "profile", "on_demand_"))
    # the handler was uninstalled on the way out
    assert signal.getsignal(signal.SIGUSR2) in (signal.SIG_DFL,
                                                signal.default_int_handler)


@pytest.mark.serving
def metrics_endpoint_under_wedged_decode_test():
    """Satellite acceptance: GET /metrics serves valid Prometheus text
    exposition from the HTTP child WITHOUT crossing the device loop — it
    answers (with admission counters, queue/breaker gauges, and the device
    loop's decode histograms merged from the heartbeat-published snapshot)
    while the device loop is wedged inside a decode."""
    from serving_robustness_test import (_StubInterface, _post, _serve_params,
                                         _spawn_serve)
    from homebrewnlp_tpu.utils.fault_injection import FaultyInterface

    params = _serve_params(serve_queue_limit=2, serve_batch_size=1,
                           serve_breaker_threshold=0,
                           serve_request_deadline_s=8.0)
    release = threading.Event()
    faulty = FaultyInterface(_StubInterface(params), block_on=release,
                             block_at={1}, block_timeout_s=30.0)
    port, stop, t = _spawn_serve(faulty)

    def scrape():
        req = urllib.request.Request(f"http://127.0.0.1:{port}/metrics")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            assert "version=0.0.4" in resp.headers["Content-Type"]
            return resp.read().decode()

    try:
        _post(port, "/health", {})     # wait for the server to come up
        types, series = _parse_exposition(scrape())
        assert types["hbnlp_serve_admission_total"] == "counter"
        assert types["hbnlp_serve_queue_depth"] == "gauge"
        assert types["hbnlp_serve_breaker_state"] == "gauge"
        assert series[("hbnlp_serve_breaker_state", "")] == 0

        # one successful decode -> the device loop's histograms reach the
        # child through the published snapshot
        status, out, _ = _post(port, "/token_completion", {"tokens": [1, 2]})
        assert status == 200
        deadline = time.monotonic() + 10
        while True:   # published on the next device-loop poll
            types, series = _parse_exposition(scrape())
            if series.get(("hbnlp_serve_decode_seconds_count", "")):
                break
            assert time.monotonic() < deadline
            time.sleep(0.1)
        assert series[("hbnlp_serve_decode_calls_total", "")] >= 1
        assert series[("hbnlp_serve_queue_wait_seconds_count", "")] >= 1
        assert series[("hbnlp_serve_batch_size_count", "")] >= 1
        assert series[("hbnlp_serve_admission_total",
                       'decision="accepted"')] >= 1

        # wedge the device loop inside a decode; /metrics must still answer
        results = {}
        th = threading.Thread(
            target=lambda: results.update(
                w=_post(port, "/token_completion", {"tokens": [3]},
                        timeout=25)),
            daemon=True)
        th.start()
        deadline = time.monotonic() + 10
        while faulty.calls < 2:        # the wedged decode is now in flight
            assert time.monotonic() < deadline
            time.sleep(0.01)
        t0 = time.monotonic()
        types, series = _parse_exposition(scrape())
        assert time.monotonic() - t0 < 2.0, "scrape crossed the device loop"
        assert series[("hbnlp_serve_admission_total",
                       'decision="accepted"')] >= 2
        # POST works too (text exposition, so not via the JSON _post helper)
        req = urllib.request.Request(f"http://127.0.0.1:{port}/metrics",
                                     data=b"{}",
                                     headers={"Content-Type":
                                              "application/json"})
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
            _parse_exposition(resp.read().decode())
        release.set()
        th.join(timeout=15)
        assert results["w"][0] == 200
    finally:
        release.set()
        stop.set()
        t.join(timeout=15)
    assert not t.is_alive()


def in_process_metrics_handler_test(fresh_registry):
    """The non-isolated branch serves /metrics from the local registry via
    the shared handlers table (no IPC state exists in-process)."""
    from serving_robustness_test import _StubInterface, _serve_params
    from homebrewnlp_tpu.infer import rest_api
    import homebrewnlp_tpu.infer.rest_api as ra
    # rebind the lazily-cached serve metrics to the fresh registry
    prev = ra._SERVE_METRICS
    ra._SERVE_METRICS = None
    try:
        stub = _StubInterface(_serve_params())
        handlers = rest_api._handlers(stub)
        handlers["/token_completion"]({"tokens": [1, 2]})
        out = handlers["/metrics"]({})
        types, series = _parse_exposition(out["_prometheus"])
        assert types["hbnlp_serve_decode_seconds"] == "histogram"
        assert series[("hbnlp_serve_decode_seconds_count", "")] == 1
        assert series[("hbnlp_serve_tokens_per_second_count", "")] == 1
    finally:
        ra._SERVE_METRICS = prev
