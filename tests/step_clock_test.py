"""The step clock (marker: telemetry; docs/OBSERVABILITY.md 'Step clock'):
``homebrewnlp_tpu/telemetry/step_clock.py`` and the benchmark's readers of
it, on the CPU.

Unit sweep on a simulated loop — an injected clock, losses whose
``is_ready`` the simulation decides, a device that runs the queued steps one
after another: the ring is bounded, queue depth and first-seen-ready follow
the completions, each cause is classified from the excess it was scripted
to make, a stall records one event, two counters and one (rate-limited)
line, and nothing ever waits for the device.  Integration: ``train()`` with
an iterator that sleeps once names the stall ``data``; the dispatch span's
annotation carries the step number under a capture; the four per-layer
readers reduce a synthetic ring by index."""
import os
import time
import types

import jax
import pytest

from homebrewnlp_tpu import telemetry
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.telemetry import compiles, step_clock
from homebrewnlp_tpu.telemetry import events as flight

pytestmark = pytest.mark.telemetry

STEP_S = 1.0


@pytest.fixture
def fresh():
    """A registry and a flight recorder of the test's own."""
    prev = telemetry.set_registry(telemetry.Registry())
    prev_rec = flight.set_recorder()
    yield telemetry.registry(), flight.recorder()
    flight.set_recorder(prev_rec)
    telemetry.set_registry(prev)


class _Loss:
    """A step's loss: ready once the simulated device is past ``done``,
    and never to be waited for."""

    def __init__(self, loop):
        self.loop, self.done, self.asked = loop, None, 0

    def is_ready(self):
        self.asked += 1
        return self.loop.t >= self.done

    def block_until_ready(self):
        raise AssertionError("the step clock waited for the device")


class _Loop:
    """The benchmark driver's loop on a simulated clock: dispatch a step,
    do the turn's host work, then wait for the step dispatched ``ahead``
    steps ago.  The device runs what is queued, one step after another.
    ``turns[k]`` scripts turn k: seconds by span site (``data/next`` ...),
    ``dispatch`` / ``cpu`` of the dispatch, ``gc``, ``sleep`` (the host
    away outside every span), ``compile`` (a backend compile in the
    dispatch) and ``device`` (extra seconds the step takes on the chip)."""

    def __init__(self, monkeypatch, ahead=2, **clock_kw):
        self.t = 100.0
        self.cpu = 0
        self.ahead = ahead
        self.lines = []
        self.monkeypatch = monkeypatch
        self.clock = step_clock.StepClock(
            clock_ns=lambda: int(self.t * 1e9), cpu_ns=lambda: self.cpu,
            out=self.lines.append, **clock_kw)
        self.losses = []
        self.free = self.t          # when the device has run all it was given

    def turn(self, dispatch=0.005, cpu=None, gc=0.0, sleep=0.0, compile=0,
             device=0.0, wait=True, **sites):
        clock = self.clock
        t0 = self.t
        clock.span_opened(step_clock.DISPATCH, t0)
        self.t += dispatch
        self.cpu += int((dispatch if cpu is None else cpu) * 1e9)
        if compile:
            self.monkeypatch.setattr(compiles, "_backend_compiles",
                                     compiles.backend_compiles() + compile)
        loss = _Loss(self)
        self.free = loss.done = max(self.free, self.t) + STEP_S + device
        self.losses.append(loss)
        clock.dispatched(loss)
        clock.span_closed(step_clock.DISPATCH, t0, self.t)
        for site, seconds in sites.items():
            site = site.replace("__", "/")
            clock.span_opened(site, self.t)
            self.t += seconds
            clock.span_closed(site, self.t - seconds, self.t)
        if gc:
            clock._on_gc("start", {})
            self.t += gc
            clock._on_gc("stop", {})
        self.t += sleep
        if wait and len(self.losses) > self.ahead:
            self.t = max(self.t, self.losses[-1 - self.ahead].done)

    def run(self, steps, turns=None):
        for k in range(steps):
            self.turn(**(turns or {}).get(k, {}))
        return self.clock


def ring_is_bounded_and_keeps_the_freshest_test(monkeypatch):
    clock = _Loop(monkeypatch, capacity=8).run(30)
    assert [e.index for e in clock.ring] == list(range(22, 30))
    assert clock.steps == 30 and clock.entry(21) is None
    assert clock.entry(25).index == 25
    assert [e.index for e in clock.entries(20, 24)] == [22, 23]
    assert len(clock._pending) <= 3 and len(clock._intervals) <= \
        step_clock.MEDIAN_OVER


def queue_depth_and_first_seen_ready_test(monkeypatch):
    """Under a run-ahead of two, step k is first seen done by the enter of
    step k + 3, which is when the device finished it; the differences are
    the device's step time; every enter past the queue's filling reads
    depth 2.  A step seen by no later enter stays unknown."""
    loop = _Loop(monkeypatch)
    clock = loop.run(12)
    ring = list(clock.ring)
    assert [e.depth for e in ring] == [0, 1] + [2] * 10
    for e in ring[:9]:
        assert e.ready_at == e.index + 3
        assert e.ready_ns == ring[e.index + 3].enter_ns
        assert e.ready_ns == int(loop.losses[e.index].done * 1e9)
    assert all(e.interval_ns == pytest.approx(STEP_S * 1e9, abs=2)
               for e in ring[1:9])
    assert [e.ready_ns for e in ring[9:]] == [None] * 3
    assert clock.completed == 9 and not loop.lines
    # an enter that finds several done shares the time since the last evenly
    loop.turn(wait=False)
    loop.t += 10
    loop.turn()
    assert [e.ready_at for e in clock.entries(9, 13)] == [12, 13, 13, 13]
    assert len({e.interval_ns for e in clock.entries(10, 13)}) == 1


CASES = {
    # cause: (the turn that makes it, the turn(s) it is scripted on)
    "compile": dict(dispatch=2.5, compile=1),
    "dispatch": dict(dispatch=2.5),
    "descheduled": dict(dispatch=2.5, cpu=0.004),
    "data": dict(data__next=3.4, data__place=0.1),
    "gc": dict(gc=3.5),
    "log_or_save": dict(train__metric_log=0.5, train__checkpoint_save=2.5,
                        train__eval=0.5),
    # the host away outside every span, long enough to drain the queue
    "host_other": dict(sleep=3.5),
    # the chip itself: the host did nothing unusual and the queue stayed full
    "device": dict(device=0.5),
}


@pytest.mark.parametrize("cause", sorted(CASES))
def stall_cause_test(monkeypatch, fresh, cause):
    registry, recorder = fresh
    loop = _Loop(monkeypatch)
    clock = loop.run(24, {12: CASES[cause]})
    stalls = recorder.events("stall")
    assert [e["cause"] for e in stalls] == [cause], (stalls, loop.lines)
    ev, = stalls
    assert ev["excess_s"] == pytest.approx(0.5, abs=0.02)
    assert ev["median_s"] == pytest.approx(STEP_S)
    if cause == "device":
        # a freeze shorter than the queued work costs the device nothing;
        # what did cost it left the queue as full as it was
        assert ev["step"] == 12 and (ev["depth_from"], ev["depth_to"]) == \
            (2, 2) and ev["depth_min"] == 2
    elif cause in ("data", "gc", "log_or_save", "host_other"):
        # the host came back to an empty queue
        assert ev["depth_min"] == 0
    assert (cause in step_clock.HOST_CAUSES) == (cause != "device")
    line, = loop.lines
    assert line.startswith("step clock: step") and f"cause {cause};" in line
    snap = registry.snapshot()
    assert snap[step_clock.STALLS_METRIC]["series"] == {(cause,): 1}
    assert snap[step_clock.STALL_SECONDS_METRIC]["series"][(cause,)] == \
        pytest.approx(ev["excess_s"])
    assert clock.steps == 24


def short_freeze_behind_a_full_queue_is_no_stall_test(monkeypatch, fresh):
    """A host that loses less than the queued work (0.5 s in gc, in the data
    iterator, or away) while two steps wait on the device costs the device
    nothing, and the clock says nothing.  Nor does it when the host comes
    back late enough to SEE a step 0.4 s late (1.4 s away, the queue still
    two deep when it is back): the next interval is as much too short, which gives it back
    — in the live rule and in the benchmark's readers alike."""
    from benchmark.lib import step_clock_readers as r
    _, recorder = fresh
    loop = _Loop(monkeypatch)
    loop.run(30, {8: dict(gc=0.5), 12: dict(data__next=0.5),
                  16: dict(sleep=0.5), 20: dict(data__next=1.4)})
    assert recorder.events("stall") == [] and loop.lines == []
    late = loop.clock.entry(18)
    assert late.interval_ns == pytest.approx(1.4e9, abs=1e7)
    assert loop.clock.entry(19).interval_ns == pytest.approx(0.6e9, abs=1e7)
    assert min(e.depth for e in loop.clock.entries(2, 30)) == 2
    run, window_s = _driver_run(monkeypatch, 30, {20: dict(data__next=1.4)})
    assert window_s == pytest.approx(30 * STEP_S + 0.005)
    assert r.stall_share(run) < 0.1 and r.stall_share(run, True) == 0
    assert r.interval_max_over_median(run) < 1.02


def stall_records_event_counters_and_one_line_test(monkeypatch, fresh):
    """Every stall: one event and the two counters.  The printed line: at
    most one in ``LINE_EVERY_NS``."""
    registry, recorder = fresh
    loop = _Loop(monkeypatch)
    loop.run(40, {10: dict(device=0.5), 12: dict(device=0.75),
                  30: dict(data__next=4.0)})
    stalls = recorder.events("stall")
    assert [(e["step"], e["cause"]) for e in stalls] == \
        [(10, "device"), (12, "device"), (30, "data")]
    for key in ("interval_s", "median_s", "excess_s", "steps", "compiles",
                "dispatch_s", "cpu_s", "data_next_s", "data_place_s",
                "metric_log_s", "checkpoint_save_s", "eval_s", "gc_s",
                "depth_from", "depth_to", "depth_min"):
        assert key in stalls[0], key
    # the second stall came 2 s after the first: no second line; the third
    # came 20 s later
    assert len(loop.lines) == 2
    assert loop.lines[0] == (
        "step clock: step 10 took 1.500 s for a median of 1.000: +0.500 s, "
        "cause device; dispatch 5 ms (cpu 5 ms), data/next 0, data/place 0, "
        "log/save/eval 0, gc 0, compiles 0, queue depth 2 -> 2")
    assert "steps 28-30 took 4.005 s for a median of 1.000 each: +1.005 s, " \
           "cause data; " in loop.lines[1]
    assert "data/next 4000 ms" in loop.lines[1]
    assert "queue depth 2 -> 0" in loop.lines[1]
    series = registry.snapshot()[step_clock.STALLS_METRIC]["series"]
    assert series == {("device",): 2, ("data",): 1}
    seconds = registry.snapshot()[step_clock.STALL_SECONDS_METRIC]["series"]
    assert seconds[("device",)] == pytest.approx(1.25)
    # telemetry off: nothing per step reached the registry
    assert step_clock.STEP_SECONDS_METRIC not in registry.snapshot()


def compiled_steps_are_no_sample_of_the_usual_step_test(monkeypatch, fresh):
    """The steps that compile (a run's first) do not enter the running
    median, and no step is judged before ``MIN_INTERVALS`` usual ones."""
    _, recorder = fresh
    loop = _Loop(monkeypatch)
    clock = loop.run(5, {0: dict(dispatch=60.0, compile=3),
                         4: dict(device=0.5)})
    loop.run(6)
    assert list(clock._intervals)[0] == pytest.approx(STEP_S * 1e9, abs=2)
    assert clock.entry(0).compiles == 3 and clock.entry(1).compiles == 0
    assert [e["step"] for e in recorder.events("stall")] == [4]


def recording_clock_feeds_the_step_histogram_test(monkeypatch, fresh):
    registry, _ = fresh
    loop = _Loop(monkeypatch, record=True)
    loop.run(12)
    state = registry.snapshot()[step_clock.STEP_SECONDS_METRIC]["series"][()]
    assert sum(state["counts"]) == loop.clock.completed == 9
    assert state["sum"] == pytest.approx(
        (loop.losses[8].done - 100.0), abs=1e-6)


def _toy(tmp_path, **overrides):
    from telemetry_test import _toy_trainer
    return _toy_trainer(tmp_path, **overrides)


def clock_never_waits_test(tmp_path, monkeypatch, fresh):
    """The pattern of ``telemetry_enabled_adds_no_per_step_sync_test``: the
    trainer's steps, clock and all, call ``block_until_ready`` never — and
    a loss that is asked is asked ``is_ready`` only."""
    calls = []
    real = jax.block_until_ready
    trainer, batch = _toy(tmp_path, telemetry_enabled=True)
    state = trainer.init_state(batch())
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    for _ in range(6):
        state, metrics = trainer.step(state, trainer.place_batch(batch()))
    assert calls == []
    clock = trainer.step_clock
    assert clock is step_clock.current() and clock.steps == 6
    assert [e.index for e in clock.ring] == list(range(6))
    assert all(e.exit_ns >= e.enter_ns and e.cpu_ns > 0 for e in clock.ring)
    # place_batch's span reached the turn it closed in
    assert sum(e.data_place_ns for e in clock.ring) > 0
    real(metrics["loss"])
    trainer.step(state, batch())
    assert clock.completed == 6 and clock.entry(5).ready_at == 6
    # the simulated losses raise if waited for: 40 steps of every kind asked
    # them nothing but is_ready
    loop = _Loop(monkeypatch)
    loop.run(40, {10: dict(device=0.5), 20: dict(sleep=5.0)})
    assert all(loss.asked >= 1 for loss in loop.losses[:-3])


def train_with_a_sleeping_iterator_names_the_stall_data_test(
        tmp_path, monkeypatch, fresh, capsys):
    """``train()``, telemetry off: the data iterator sleeps 0.3 s once; the
    run's own output names the step, the excess and the cause, and the
    flight recorder holds the event (train() installs its own recorder
    target but records into the process's ring)."""
    from robustness_test import _train_cfg, _write_records
    from homebrewnlp_tpu.run import train_loop as tl
    registry, _ = fresh
    real = tl._macro_batches

    def sleepy(dataset, macro):
        for n, item in enumerate(real(dataset, macro)):
            if n == 20:
                time.sleep(0.3)
            yield item

    monkeypatch.setattr(tl, "_macro_batches", sleepy)
    cfg = _train_cfg(tmp_path, _write_records(tmp_path),
                     use_checkpointing=False, train_steps=32, buffer_size=1)
    result = tl.train(ModelParameter(cfg), log_every=4)
    assert result["final_step"] == 32
    stalls = [e for e in flight.recorder().events("stall")
              if e["cause"] == "data"]
    assert len(stalls) == 1, flight.recorder().events("stall")
    ev, = stalls
    assert 0.25 < ev["excess_s"] < 1.0 and ev["data_next_s"] >= 0.25
    assert 16 <= ev["step"] <= 24
    out = capsys.readouterr().out
    lines = [x for x in out.splitlines() if x.startswith("step clock:")]
    named = [x for x in lines if "cause data" in x]
    # one line in LINE_EVERY_NS: the host's own jitter may have had it
    assert named or len(lines) == 1, out
    for line in named:
        assert f"step {ev['step']} " in line or f"-{ev['step']} " in line
    assert registry.snapshot()[step_clock.STALLS_METRIC]["series"][
        ("data",)] == 1
    # train()'s metric log handed its span to the clock as well
    clock = step_clock.current()
    assert clock.steps == 32
    assert sum(e.metric_log_ns for e in clock.ring) > 0


def dispatch_annotation_carries_the_step_test(tmp_path, fresh):
    """Under a capture each ``train/step_dispatch`` event on the host plane
    has the stat ``step``: the index of its entry in the ring."""
    import glob
    from jax.profiler import ProfileData
    trainer, batch = _toy(tmp_path)
    state = trainer.init_state(batch())
    for _ in range(2):
        state, _ = trainer.step(state, batch())
    telemetry.start_capture(str(tmp_path / "capture"))
    try:
        for _ in range(3):
            state, metrics = trainer.step(state, batch())
        jax.block_until_ready(metrics["loss"])
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "capture" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == step_clock.DISPATCH:
                    found.append((e.start_ns, dict(e.stats)["step"]))
    assert [int(step) for _, step in sorted(found)] == [2, 3, 4]
    assert trainer.step_clock.steps == 5


# ---- the benchmark's readers ------------------------------------------------

def _run(loop, steps, window_s):
    return types.SimpleNamespace(
        notes=[], result=types.SimpleNamespace(
            counters={"steps": steps}, spans={"window_s": window_s}))


def _driver_run(monkeypatch, window, turns=None, traced=0):
    """The train driver's order on the simulated loop: two warm-up steps and
    a fence, ``window`` steps and a fence, then — later, after the profiler
    has started — ``traced`` steps the readers must leave out."""
    from benchmark.drivers.train import WARMUP_STEPS
    loop = _Loop(monkeypatch)
    loop.turn(dispatch=30.0, compile=1)
    loop.turn()
    loop.t = loop.losses[-1].done
    t0 = loop.t
    loop.losses = []
    loop.run(window, {k - WARMUP_STEPS: v for k, v in (turns or {}).items()})
    loop.t = loop.losses[-1].done
    window_s = loop.t - t0
    loop.t += 3.0
    loop.losses = []
    loop.run(traced)
    monkeypatch.setattr(step_clock, "_current", loop.clock)
    return _run(loop, window, window_s), window_s


def readers_read_zero_with_no_stall_test(monkeypatch, fresh):
    from benchmark.lib import step_clock_readers as r
    run, window_s = _driver_run(monkeypatch, 20, traced=4)
    assert window_s == pytest.approx(20 * STEP_S + 0.005)
    # step 0's interval runs from the window's first enter: it holds that
    # step's dispatch, 5 ms of 20 s
    assert r.stall_share(run) == pytest.approx(100 * 0.005 / window_s, rel=0.05)
    assert r.stall_share(run, host_only=True) == 0
    assert r.interval_max_over_median(run) == pytest.approx(1.005, abs=1e-3)
    assert r.starved_dispatch_share(run) == 0
    assert any("beyond it" in n for n in run.notes)


def readers_take_the_window_by_index_test(monkeypatch, fresh):
    """One device stall and one data stall in the window, a long fence and
    profiler start after it: the share is the two excesses over the window,
    the host's part the data stall alone, and the traced steps — whose first
    enter sees the window's last steps late — are left out."""
    from benchmark.lib import step_clock_readers as r
    run, window_s = _driver_run(
        monkeypatch, 30, {10: dict(device=0.5), 20: dict(data__next=3.8)},
        traced=4)
    found = r.window(run)
    big = sorted((s for s in found["stalls"] if s["excess_s"] > 0.1),
                 key=lambda s: s["step"])
    assert [(s["cause"], s["steps"]) for s in big] == [("device", 1),
                                                       ("data", 3)]
    assert big[0]["step"] == 10 and 20 <= big[1]["step"] <= 22
    assert big[0]["excess_s"] == pytest.approx(0.5, abs=1e-3)
    assert big[1]["excess_s"] == pytest.approx(0.8, abs=0.02)
    assert r.stall_share(run) == pytest.approx(100 * 1.3 / window_s, rel=0.03)
    assert r.stall_share(run, host_only=True) == pytest.approx(
        100 * 0.8 / window_s, rel=0.03)
    assert r.interval_max_over_median(run) == pytest.approx(1.5, abs=1e-3)
    # one enter of the 28 past the queue's filling found the device empty
    assert r.starved_dispatch_share(run) == pytest.approx(100 / 28)
    note, = [n for n in run.notes if "beyond it" in n]
    assert "step 10 +0.500000 s device" in note and " data" in note
    # an untraced run (no step after the window) reads the same
    again, _ = _driver_run(
        monkeypatch, 30, {10: dict(device=0.5), 20: dict(data__next=3.8)})
    assert r.stall_share(again) == pytest.approx(r.stall_share(run))
    # a stall among the window's last steps, which no enter of the window
    # sees done, is in what the closing fence leaves of window_s
    late, late_s = _driver_run(monkeypatch, 30, {29: dict(device=0.6)},
                               traced=4)
    assert r.stall_share(late) == pytest.approx(100 * 0.6 / late_s, rel=0.05)
    assert r.stall_share(late, host_only=True) == 0


def benchmark_lists_the_four_metrics_test():
    """``BENCHMARK.json``: the four entries in PR 51's order, each on every
    train cell, each with its file agreeing on layer and end-to-end
    metric."""
    import json
    from benchmark.lib import cell as cell_mod
    with open(os.path.join(cell_mod.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = ["step_stall_share", "step_stall_host_share",
             "step_interval_max_over_median", "device_starved_dispatch_share"]
    mine = [m for m in bench["per_layer"] if m["name"] in names]
    assert [m["name"] for m in mine] == names
    for entry in mine:
        mod = cell_mod.load_metric(entry["name"])
        assert entry["workloads"] == cells
        assert (entry["layer"], entry["moves"], entry["source"],
                entry["better"]) == (mod.LAYER, mod.MOVES, "program_counter",
                                     "lower")
        assert entry["layer"] == "L1_host_loop" and mod.__doc__


def gc_inside_a_span_is_the_collectors_test(monkeypatch, fresh):
    """A collection that runs inside ``data/next`` lengthens that span too:
    the stall is the collector's, not the data's."""
    _, recorder = fresh
    loop = _Loop(monkeypatch)
    real_turn = loop.turn

    def turn(**kw):
        if loop.clock.steps == 12:
            # 3.5 s in gc while the data/next span is open
            real_turn(wait=False)
            loop.clock.span_opened("data/next", loop.t)
            loop.clock._on_gc("start", {})
            loop.t += 3.5
            loop.clock._on_gc("stop", {})
            loop.clock.span_closed("data/next", loop.t - 3.5, loop.t)
        else:
            real_turn(**kw)

    loop.turn = turn
    loop.run(24)
    assert [e["cause"] for e in recorder.events("stall")] == ["gc"]


def readers_return_nothing_without_a_clock_test(monkeypatch):
    from benchmark.lib import step_clock_readers as r
    monkeypatch.setattr(step_clock, "_current", None)
    run = _run(None, 10, 10.0)
    assert r.stall_share(run) is None and run.notes
    loop = _Loop(monkeypatch, capacity=4)
    loop.run(20)
    monkeypatch.setattr(step_clock, "_current", loop.clock)
    run = _run(None, 10, 10.0)
    assert r.starved_dispatch_share(run) is None
    assert "no longer holds" in run.notes[-1]
