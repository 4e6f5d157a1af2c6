"""The trace reduction on the recorded v5e fixture (every number it gives
is pinned) and on hand-made events (collectives, which one chip has none
of)."""
import os

import pytest

from benchmark.trace import reduce as R

FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "trace", "fixtures",
    "v5e_fixture_step.xplane.pb")
SPANS = ("data_next", "dispatch", "fence")


@pytest.fixture(scope="module")
def trace():
    return R.load(FIXTURE)


@pytest.fixture(scope="module")
def reduced(trace):
    return R.reduce(trace, "bench_window", SPANS)


def fixture_holds_one_chip_and_the_harness_spans_test(trace):
    assert [d.name for d in trace.devices] == ["/device:TPU:0"]
    dev = trace.devices[0]
    assert (len(dev.ops), len(dev.modules), len(dev.async_ops)) == (63, 3, 15)
    names = [e.name for e in trace.host]
    assert names.count("bench_window") == 1
    assert [names.count(n) for n in SPANS] == [3, 3, 1]


@pytest.mark.parametrize("key,value", [
    ("window_s", 0.02168817), ("busy_s", 0.001123624),
    ("idle_s", 0.020564546), ("collective_s", 0.0),
    ("collective_exposed_s", 0.0), ("devices", 1)])
def window_busy_and_idle_test(reduced, key, value):
    assert reduced[key] == pytest.approx(value, rel=1e-9)


def busy_plus_idle_is_the_window_test(reduced):
    assert reduced["busy_s"] + reduced["idle_s"] == \
        pytest.approx(reduced["window_s"], rel=1e-12)


def module_runs_test(reduced):
    assert list(reduced["modules"]) == ["jit_fixture_step"]
    assert reduced["modules"]["jit_fixture_step"] == pytest.approx(
        [0.000374648, 0.000374945, 0.000374921], rel=1e-9)


@pytest.mark.parametrize("op,seconds,calls", [
    ("convolution_tanh_fusion", 0.000272896, 3),
    ("fusion", 0.000272793, 3), ("fusion.2", 0.000270154, 3),
    ("fusion.1", 0.000269591, 3),
    ("flash_fwd_causal.1", 2.2234e-05, 3),
    ("map_mixer_fwd_causal.1", 5.511e-06, 3)])
def per_op_sums_test(reduced, op, seconds, calls):
    assert reduced["ops"][op] == pytest.approx(seconds, rel=1e-9)
    assert reduced["calls"][op] == calls
    assert len(reduced["ops"]) == 21


def matmul_fusions_run_near_the_chips_peak_test(reduced):
    # four 2048^3 bf16 matmuls a step: a sanity bound on the trace's clock
    flops = 2 * 2048 ** 3
    for op in ("fusion", "fusion.1", "fusion.2", "convolution_tanh_fusion"):
        rate = flops * reduced["calls"][op] / reduced["ops"][op]
        assert 170e12 < rate < 197e12


@pytest.mark.parametrize("pattern,kind,seconds", [
    (r"^map_mixer_", "map_mixer_fwd_causal", 5.511e-06),
    (r"^flash_", "flash_fwd_causal", 2.2234e-05)])
def kernel_sums_test(reduced, pattern, kind, seconds):
    stats = R.kernel_stats(reduced, pattern)
    assert list(stats) == [kind]
    assert stats[kind][0] == pytest.approx(seconds, rel=1e-9)
    assert stats[kind][1] == 3
    assert R.kernel_stats(reduced, r"^no_such_kernel") == {}


def gaps_are_attributed_to_the_span_that_covers_most_test(reduced):
    # the longest gap holds the deliberate 10 ms sleep outside any span
    assert [g[0] for g in reduced["gaps"]] == \
        ["unattributed", "data_next", "data_next", "fence"]
    assert [g[1] for g in reduced["gaps"]] == pytest.approx(
        [0.013746799, 0.002693632, 0.002304847, 0.0018192], rel=1e-9)
    assert reduced["idle_by_span"] == pytest.approx(
        {"data_next": 0.006453246, "dispatch": 0.00146936,
         "fence": 0.00115697}, rel=1e-9)


def without_a_window_span_the_device_events_bound_the_window_test(trace):
    r = R.reduce(trace, None, ())
    assert r["window_s"] == pytest.approx(0.017564396, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.001123624, rel=1e-9)
    assert [g[0] for g in r["gaps"]] == ["unattributed", "unattributed"]


def breakdown_lists_the_largest_ops_and_gaps_test(reduced):
    b = R.breakdown(reduced)
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 4
    assert b["device_ops"][0][0] == \
        "convolution_tanh_fusion kOutput bf16[2048,2048]"
    assert b["device_ops"][4][0] == \
        "flash_fwd_causal.1 custom-call bf16[2,512,128]"
    assert b["idle_gaps"][0] == ["unattributed", pytest.approx(0.013746799)]


@pytest.mark.parametrize("text,short,code", [
    ("%fusion.1 = bf16[8]{0:T(8)} fusion(bf16[8]{0} %a), kind=kLoop",
     "fusion.1", "fusion"),
    ("%all-reduce-start.3 = (f32[4]{0}, f32[4]{0}) all-reduce-start(f32[4]"
     "{0} %x), replica_groups={{0,1}}", "all-reduce-start.3",
     "all-reduce-start"),
    ("%map_mixer_fwd_causal.1 = bf16[4,512,128]{2,1,0:T(8,128)(2,1)S(1)} "
     "custom-call(bf16[2,512,512]{2,1,0} %c)", "map_mixer_fwd_causal.1",
     "custom-call"),
    ("%copy-done = bf16[2,2]{1,0:T(8,128)(2,1)S(1)} copy-done((bf16[2,2]"
     "{1,0}, u32[]{:S(2)}) %copy-start)", "copy-done", "copy-done")])
def names_test(text, short, code):
    assert R.short_name(text) == short
    assert R.opcode(text) == code
    assert R.is_collective(text) == code.startswith("all-reduce")


def interval_arithmetic_test():
    assert R.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert R.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert R.subtract([(0, 3), (5, 8)], [(2, 6)]) == [(0, 2), (6, 8)]
    assert R.overlap([(0, 3), (5, 8)], [(2, 6)]) == 2
    assert R.clip([(0, 3), (5, 8)], 1, 6) == [(1, 3), (5, 6)]


def _ev(name, start, end):
    return R.Event(name, float(start), float(end))


def collective_time_and_its_exposed_part_test():
    """Two chips; on each an async all-reduce is in flight over [100, 400)
    while a fusion runs over [100, 300) — 100 ns of it exposed — and a
    synchronous all-gather over [500, 600) is exposed entirely."""
    def chip(n):
        return R.Device(
            f"/device:TPU:{n}",
            ops=[_ev("%all-reduce-start.1 = f32[4] all-reduce-start(f32[4] "
                     "%g)", 100, 101),
                 _ev("%fusion.7 = f32[4] fusion(f32[4] %a), kind=kLoop",
                     101, 300),
                 _ev("%all-reduce-done.1 = f32[4] all-reduce-done(f32[4] "
                     "%s)", 300, 400),
                 _ev("%all-gather.2 = f32[8] all-gather(f32[4] %b)", 500,
                     600)],
            modules=[_ev("jit_step_fn(1)", 100, 600)],
            async_ops=[_ev("%all-reduce-start.1 = f32[4] all-reduce-start("
                           "f32[4] %g)", 100, 400)])
    r = R.reduce(R.Trace([chip(0), chip(1)], []), None, ())
    assert [d["name"] for d in r["per_device"]] == \
        ["/device:TPU:0", "/device:TPU:1"]
    assert r["window_s"] == pytest.approx(500e-9)
    assert r["busy_s"] == pytest.approx(400e-9)      # 100..400 and 500..600
    assert r["collective_s"] == pytest.approx(400e-9)
    # 100..101 and 300..400 of the all-reduce, 500..600 of the all-gather
    assert r["collective_exposed_s"] == pytest.approx(201e-9)
    assert r["modules"] == {"jit_step_fn": [pytest.approx(500e-9)]}


def a_collective_inside_a_scan_is_exposed_test():
    """The ``while`` of a scan spans its body's instructions; a synchronous
    all-reduce in the body is still time in which nothing else runs."""
    dev = R.Device("/device:TPU:0", ops=[
        _ev("%while.8 = (s32[]) while((s32[]) %t), body=%b", 0, 1000),
        _ev("%fusion.1 = f32[4] fusion(f32[4] %a), kind=kLoop", 0, 400),
        _ev("%all-reduce.3 = f32[4] all-reduce(f32[4] %g)", 400, 700),
        _ev("%fusion.2 = f32[4] fusion(f32[4] %a), kind=kLoop", 700, 950)],
        modules=[_ev("jit_step_fn(1)", 0, 1000)])
    r = R.reduce(R.Trace([dev], []), None, ())
    assert r["busy_s"] == pytest.approx(1000e-9)
    assert r["collective_s"] == pytest.approx(300e-9)
    assert r["collective_exposed_s"] == pytest.approx(300e-9)
    assert r["ops"] == pytest.approx({
        "while.8": 50e-9, "fusion.1": 400e-9, "all-reduce.3": 300e-9,
        "fusion.2": 250e-9})


def a_trace_without_a_device_plane_is_refused_test():
    with pytest.raises(ValueError):
        R.reduce(R.Trace([], [_ev("bench_window", 0, 10)]), "bench_window")
