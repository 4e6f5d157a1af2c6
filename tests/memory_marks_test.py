"""The chip's memory from inside (telemetry/memory.py; docs/OBSERVABILITY.md
'Device memory'): the one reader of ``memory_stats()``, the marks of
``Trainer.init_state`` / ``Trainer.step`` / ``train()``, what the train
state is made of, and the benchmark's three readers of the gauges.

XLA:CPU reports no memory, so every case injects its readings: fake devices
into the reader, or ``memory.device_stats`` patched where the trainer reads
its own local devices."""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu import telemetry
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.telemetry import events, memory

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GB = 10 ** 9
LIMIT = 16909336064


def _raw(in_use, reserved, **more):
    """A reading in the TPU runtime's own keys."""
    return {"num_allocs": 7, "bytes_in_use": in_use, "bytes_reserved": reserved,
            "peak_bytes_in_use": in_use + GB, "peak_bytes_reserved": reserved,
            "largest_free_block_bytes": LIMIT - in_use - reserved,
            "bytes_limit": LIMIT,
            "bytes_reservable_limit": LIMIT - in_use, **more}


class _Device:
    def __init__(self, id_, raw):
        self.id, self._raw = id_, raw

    def memory_stats(self):
        if isinstance(self._raw, Exception):
            raise self._raw
        return self._raw


@pytest.fixture
def fresh():
    """A registry and a flight recorder of the test's own."""
    prev_registry = telemetry.set_registry(telemetry.Registry())
    prev_recorder = events.set_recorder(events.FlightRecorder())
    yield telemetry.registry(), events.recorder()
    events.set_recorder(prev_recorder)
    telemetry.set_registry(prev_registry)


def _hbm(registry):
    return registry.snapshot().get(memory.HBM_METRIC, {}).get("series", {})


def _report_memory(monkeypatch, in_use=lambda d: 3 * GB,
                   reserved=lambda d: 2 * GB):
    """The local (CPU) devices report memory, as a TPU's would."""
    calls = []

    def stats(device):
        calls.append(device.id)
        return memory.device_stats(
            _Device(device.id, _raw(in_use(device), reserved(device))))

    monkeypatch.setattr(memory, "read", lambda devices=None: [
        (d, stats(d)) for d in (jax.local_devices() if devices is None
                                else devices)])
    return calls


# ---- the reader --------------------------------------------------------------

@pytest.mark.parametrize("fullest", [0, 1, 2, 3])
def fullest_device_is_published_test(fresh, fullest):
    """Four local devices: the gauges and the event carry the one whose
    ``in_use + reserved`` is largest — not the first, not the one with the
    most in use — and the reading every device's footprint."""
    registry, recorder = fresh
    devices = [_Device(i, _raw(2 * GB + (i == (fullest + 1) % 4) * GB // 2,
                               GB + (i == fullest) * GB))
               for i in range(4)]
    reading = memory.mark("state_ready", devices)
    assert reading.device.id == fullest
    assert reading.footprints == {
        d.id: d._raw["bytes_in_use"] + d._raw["bytes_reserved"]
        for d in devices}
    raw = devices[fullest]._raw
    assert _hbm(registry) == {
        ("state_ready", kind): float(raw[key])
        for kind, key in memory.RUNTIME_KEYS.items()}
    assert set(memory.KINDS) == {"in_use", "reserved", "peak_in_use",
                                 "peak_reserved", "largest_free_block",
                                 "limit", "reservable_limit"}
    event, = recorder.events("memory")
    assert event["point"] == "state_ready" and event["device"] == fullest
    assert {k: event[k] for k in memory.KINDS} == reading.stats
    spans = registry.snapshot()[telemetry.SPAN_METRIC]["series"]
    assert sum(spans[("memory/state_ready",)]["counts"]) == 1


@pytest.mark.parametrize("raw", [None, {}, RuntimeError("a described device")],
                         ids=["none", "empty", "raises"])
def silent_backend_leaves_no_series_test(fresh, raw):
    """A backend without memory statistics: absent, never 0 — no series,
    no event, and the start-up line says so."""
    registry, recorder = fresh
    devices = [_Device(i, raw) for i in range(2)]
    assert memory.read(devices) == [(d, None) for d in devices]
    assert memory.mark("state_ready", devices) is None
    assert memory.HBM_METRIC not in registry.snapshot()
    assert recorder.events("memory") == []
    assert memory.publish_state(None, {"params": []}) == memory.NOT_REPORTED \
        == "memory: not reported by this backend"
    assert memory.STATE_METRIC not in registry.snapshot()
    assert memory.loaded_line(None) is None


def missing_kinds_stay_absent_test(fresh):
    """A runtime that reports some of the keys: the others get no series."""
    registry, _ = fresh
    memory.mark("running", [_Device(0, {"bytes_in_use": 5, "bytes_limit": 9})])
    assert _hbm(registry) == {("running", "in_use"): 5.0,
                              ("running", "limit"): 9.0}


def real_cpu_backend_reports_nothing_test(fresh):
    registry, _ = fresh
    assert all(stats is None for _, stats in memory.read())
    assert memory.mark("state_ready") is None
    assert memory.HBM_METRIC not in registry.snapshot()


def program_calls_memory_stats_in_one_module_test():
    """``grep -rn "memory_stats()" homebrewnlp_tpu main.py scripts`` shows
    docstrings and ONE call: the reader's."""
    import ast
    paths = [os.path.join(REPO, "main.py")]
    for root in ("homebrewnlp_tpu", "scripts"):
        for folder, _, files in os.walk(os.path.join(REPO, root)):
            paths += [os.path.join(folder, f) for f in files
                      if f.endswith(".py")]
    calls = []
    for path in paths:
        with open(path) as f:
            tree = ast.parse(f.read())
        calls += [os.path.relpath(path, REPO) for node in ast.walk(tree)
                  if isinstance(node, ast.Call)
                  and isinstance(node.func, ast.Attribute)
                  and node.func.attr == "memory_stats"]
    assert calls == ["homebrewnlp_tpu/telemetry/memory.py"]


@pytest.mark.parametrize("reported", [False, True])
def placement_line_keeps_its_text_test(monkeypatch, reported):
    """``placement_report`` and ``hbm_capacity`` read through the reader;
    the ``placement:`` line is the one log parsers know."""
    from homebrewnlp_tpu.core import sharding as shardlib
    from homebrewnlp_tpu.utils import flops
    if reported:
        _report_memory(monkeypatch)
    variables = {"w": jnp.zeros((4, 4))}
    ids = [d.id for d in jax.local_devices()]
    held = 3 * GB if reported else None
    if len(ids) == 1:
        assert shardlib.placement_report(variables, None) == (
            f"placement: mesh=None parameter shards on 1/1 local devices; "
            f"bytes_in_use={{{ids[0]}: {held}}}")
    else:
        with pytest.raises(RuntimeError, match=re.escape(
                f"bytes_in_use={ {i: held for i in ids} }")):
            shardlib.placement_report(variables, None)
    assert flops.hbm_capacity(_Device(0, _raw(1, 2))) == (LIMIT,
                                                          "memory_stats")
    assert flops.hbm_capacity(jax.devices()[0])[1] == "table:cpu"


# ---- what the state is made of ----------------------------------------------

@pytest.mark.parametrize("sharded", [False, True])
def state_split_sums_to_the_leaves_bytes_test(fresh, sharded):
    """A toy ``TrainState`` with float32 masters beside bfloat16 moments:
    the split by kind and dtype is the leaves' bytes on the device — the
    whole leaf, or its shard on a mesh."""
    from homebrewnlp_tpu.train import TrainState
    registry, _ = fresh
    devices = jax.local_devices()
    ways = 1
    variables = {"w": jnp.ones((8, 64), jnp.float32),
                 "b": jnp.ones((64,), jnp.bfloat16)}
    slots = {"w": {"m": jnp.ones((8, 64), jnp.bfloat16),
                   "v": jnp.ones((8, 64), jnp.float32)},
             "b": {"m": jnp.ones((64,), jnp.bfloat16)}}
    if sharded:
        if len(devices) < 2:
            pytest.skip("one device")
        ways = 2
        mesh = jax.sharding.Mesh(np.asarray(devices[:2]), ("model",))
        by_rows = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("model"))
        # "w" and its slots by rows over two devices, "b" on the first only
        variables["w"] = jax.device_put(variables["w"], by_rows)
        slots["w"] = {k: jax.device_put(v, by_rows)
                      for k, v in slots["w"].items()}
    state = TrainState(variables, slots, jnp.asarray(0, jnp.int32))
    device = devices[0]
    params = memory.leaves_bytes_on(state.variables.values(), device)
    opt = memory.leaves_bytes_on(jax.tree_util.tree_leaves(state.opt_state),
                                 device)
    assert params == {"float32": 8 * 64 * 4 // ways, "bfloat16": 64 * 2}
    assert opt == {"bfloat16": 8 * 64 * 2 // ways + 64 * 2,
                   "float32": 8 * 64 * 4 // ways}
    if sharded:
        # the second device holds the halves of "w" and nothing of "b"
        assert memory.leaves_bytes_on(state.variables.values(), devices[1]) \
            == {"float32": 8 * 64 * 2}
    else:
        assert sum(params.values()) + sum(opt.values()) == sum(
            x.nbytes for x in jax.tree_util.tree_leaves(state[:2]))
    in_use = 10_000
    reading = memory.Reading("state_ready", device,
                             {"in_use": in_use, "limit": 100_000,
                              "reservable_limit": 90_000}, {device.id: in_use})
    line = memory.publish_state(reading, {
        "params": state.variables.values(),
        "opt_slots": jax.tree_util.tree_leaves(state.opt_state)})
    total = sum(params.values()) + sum(opt.values())
    assert registry.snapshot()[memory.STATE_METRIC]["series"] == {
        ("params",): float(sum(params.values())),
        ("opt_slots",): float(sum(opt.values()))}
    assert line.startswith("memory: at state_ready device ")
    assert f"in_use {in_use} bytes = 10.00% of limit 100000 " \
           f"(reservable 90000)" in line
    assert f"train state {total} = params {sum(params.values())} (bfloat16 " \
           f"128, float32 {params['float32']}) + opt_slots" in line
    assert line.endswith(f"batch, layout padding and runtime {in_use - total}")


# ---- the trainer's marks -----------------------------------------------------

def _toy_trainer(tmp_path, **overrides):
    from telemetry_test import _toy_trainer as toy
    return toy(tmp_path, **overrides)


class _Loss:
    """A step's loss as the probe sees it: ready when the test says so,
    and never to be waited for."""

    def __init__(self):
        self.ready, self.asked = False, 0

    def is_ready(self):
        self.asked += 1
        return self.ready

    def block_until_ready(self):
        raise AssertionError("the mark waited for the device")


def step_loaded_waits_for_the_building_steps_loss_test(tmp_path, fresh,
                                                       monkeypatch):
    """The mark is made at the first call whose enter the step clock found
    the FIRST step's loss ready, exactly once, without a wait and without a
    poll of its own: the clock's ring is what it reads (the clock goes on
    asking the steps after it, once each enter)."""
    registry, recorder = fresh
    _report_memory(monkeypatch)
    trainer, batch = _toy_trainer(tmp_path)
    state = trainer.init_state(batch())
    monkeypatch.setattr(jax, "block_until_ready", _Loss.block_until_ready)
    first, later = _Loss(), _Loss()
    real_step = []

    def step_fn(state, batch, rng):
        # the jitted step's place: what it returns is what the clock polls
        real_step.append(1)
        return state, {"loss": first if len(real_step) == 1 else later}

    trainer._step_fn = step_fn
    for _ in range(4):                          # the step that built, and
        trainer.step(state, batch())            # three while it still runs
    assert first.asked == 3 and recorder.events("memory")[2:] == []
    assert trainer.step_memory_line is None and not trainer._step_loaded
    assert not any(k[0] == "step_loaded" for k in _hbm(registry))
    first.ready = True
    trainer.step(state, batch())
    assert first.asked == 4 and trainer.step_clock.completed == 1
    assert [e["point"] for e in recorder.events("memory")][2:] == \
        ["step_loaded"]
    assert trainer._step_loaded and later.asked == 1
    assert ("step_loaded", "reserved") in _hbm(registry)
    line = trainer.step_memory_line
    assert line.startswith("memory: at step_loaded device ")
    assert f"footprint {5 * GB} bytes = {100 * 5 * GB / LIMIT:.2f}% of " \
           f"limit {LIMIT} = in_use {3 * GB} + step scratch {2 * GB} " \
           f"({100 * 2 * GB / LIMIT:.2f}%; reservable {LIMIT - 3 * GB})" in line
    assert f"largest free block {LIMIT - 5 * GB}" in line
    assert f"footprint by local device " \
           f"{ {d.id: 5 * GB for d in jax.local_devices()} }" in line


@pytest.mark.parametrize("reported", [False, True])
def trainer_marks_each_point_once_test(tmp_path, fresh, monkeypatch, reported):
    """``init_state`` marks ``params_placed`` and ``state_ready``, the step
    after the first one's loss is ready ``step_loaded``: one flight-recorder
    event a mark, the gauges of all three points, and nothing at all on a
    backend that reports nothing."""
    registry, recorder = fresh
    if reported:
        sweeps = _report_memory(monkeypatch)
    trainer, batch = _toy_trainer(tmp_path)
    assert trainer.state_memory_line == memory.NOT_REPORTED
    state = trainer.init_state(batch())
    points = ["params_placed", "state_ready"]
    state, metrics = trainer.step(state, batch())
    assert not trainer._step_loaded
    assert trainer.step_clock._pending[0][1] is metrics["loss"]
    jax.block_until_ready(metrics["loss"])      # the test's wait, not the mark's
    for _ in range(3):
        state, _ = trainer.step(state, batch())
    assert trainer._step_loaded
    spans = registry.snapshot()[telemetry.SPAN_METRIC]["series"]
    for point in points + ["step_loaded"]:
        assert sum(spans[(f"memory/{point}",)]["counts"]) == 1, point
    if not reported:
        assert recorder.events("memory") == [] and not _hbm(registry)
        assert trainer.state_memory_line == memory.NOT_REPORTED
        assert trainer.step_memory_line is None
        return
    assert [e["point"] for e in recorder.events("memory")] \
        == points + ["step_loaded"]
    assert len(sweeps) == 3 * len(jax.local_devices())
    assert {key[0] for key in _hbm(registry)} == set(points + ["step_loaded"])
    held = registry.snapshot()[memory.STATE_METRIC]["series"]
    assert held[("params",)] == sum(
        v.nbytes for v in state.variables.values())
    assert held[("opt_slots",)] == sum(
        v.nbytes for v in jax.tree_util.tree_leaves(state.opt_state))
    assert trainer.state_memory_line.startswith("memory: at state_ready")
    assert trainer.step_memory_line.startswith("memory: at step_loaded")


@pytest.mark.parametrize("reported", [False, True])
def train_prints_the_memory_lines_test(tmp_path, fresh, monkeypatch, capsys,
                                       reported):
    """``train()``: the state's line after ``remat stash:``, the loaded
    step's line once, and under ``telemetry_enabled`` point ``running`` at
    the log cadence; on XLA:CPU ``memory: not reported by this backend``,
    no series, and nothing raises."""
    from robustness_test import _train_cfg, _write_records
    from homebrewnlp_tpu.run import train_loop as tl
    registry, recorder = fresh
    if reported:
        _report_memory(monkeypatch)
    cfg = _train_cfg(tmp_path, _write_records(tmp_path),
                     use_checkpointing=False, train_steps=8,
                     telemetry_enabled=True)
    tl.train(ModelParameter(cfg), log_every=2)
    lines = capsys.readouterr().out.splitlines()
    shown = [line for line in lines if line.startswith("memory:")]
    after = lines[[i for i, line in enumerate(lines)
                   if line.startswith("remat stash:")][0] + 1]
    if not reported:
        assert shown == [memory.NOT_REPORTED] == [after]
        assert memory.HBM_METRIC not in registry.snapshot()
        assert memory.STATE_METRIC not in registry.snapshot()
        return
    assert after == shown[0] and shown[0].startswith("memory: at state_ready")
    assert [line[:26] for line in shown[1:]] == ["memory: at step_loaded dev"]
    logs = len(recorder.events("step"))
    points = [e["point"] for e in recorder.events("memory")]
    assert points.count("running") == logs >= 3
    assert points.count("step_loaded") == 1
    assert ("running", "in_use") in _hbm(registry)


# ---- the benchmark's readers of the gauges ----------------------------------

def _run(**device):
    from benchmark.lib.result import Result, Run
    return Run(cell=None, config={}, trace=None, result=Result(
        end_to_end={}, correct=True, checks={}, attempted=0, failed=0,
        device=device, spans={}, counters={"memory_limit_bytes": LIMIT}))


def _metric(name):
    import importlib
    return importlib.import_module(f"benchmark.metrics.{name}")


METRICS = {"hbm_step_footprint_share": 100 * (3.5 + 2) * GB / LIMIT,
           "hbm_state_share": 100 * 3 * GB / LIMIT,
           "hbm_step_scratch_share": 100 * 2 * GB / LIMIT}


@pytest.mark.parametrize("name", sorted(METRICS))
def benchmark_reader_without_gauges_reads_nothing_test(fresh, name):
    """A parent without the gauges, or XLA:CPU: ``None`` and a note that
    says which series is missing — never a raise, never a 0."""
    run = _run()
    assert _metric(name).read(run) is None
    assert len(run.notes) == 1 and run.notes[0].startswith("MISSING: ")
    assert memory.HBM_METRIC in run.notes[0]


@pytest.mark.parametrize("name", sorted(METRICS))
def benchmark_reader_reads_the_gauges_test(fresh, name):
    """The three shares from the gauges of ``state_ready`` and
    ``step_loaded``, by label — with the constant labels a multi-host run
    stamps on every series too; state + scratch + what ``in_use`` grew by
    is the footprint."""
    registry, _ = fresh
    prev = telemetry.set_constant_labels({"process": "0"})
    try:
        memory.mark("state_ready", [_Device(0, _raw(3 * GB, 0))])
        memory.mark("step_loaded", [_Device(0, _raw(3 * GB + GB // 2,
                                                    2 * GB))])
        held = registry.gauge(memory.STATE_METRIC, "", ("kind",))
        held.labels("params").set(2 * GB)
        held.labels("opt_slots").set(GB // 4)
        run = _run(memory_peak_bytes=5 * GB)
        value = _metric(name).read(run)
    finally:
        telemetry.set_constant_labels(prev)
    assert value == pytest.approx(METRICS[name], rel=1e-12)
    assert not [n for n in run.notes if n.startswith("MISSING")]
    notes = " | ".join(run.notes)
    if name == "hbm_step_scratch_share":
        grew = 100 * (GB // 2) / LIMIT
        assert f"in_use grew {GB // 2} bytes = {grew:.2f}%" in notes
        assert f"of reservable_limit {LIMIT - 3 * GB - GB // 2}" in notes
        assert METRICS["hbm_state_share"] + value + grew == pytest.approx(
            METRICS["hbm_step_footprint_share"])
    elif name == "hbm_state_share":
        assert f"params {2 * GB} bytes, opt_slots {GB // 4} bytes" in notes
    else:
        assert f"memory_peak_bytes of this run: {5 * GB}" in notes


def benchmark_lists_the_three_metrics_test():
    """``BENCHMARK.json``: the three entries, each on every train cell, each
    with its file agreeing on layer and end-to-end metric."""
    import json
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = [w["name"] for w in bench["workloads"]]
    for name in METRICS:
        entry, mod = entries[name], _metric(name)
        assert entry["workloads"] == cells
        assert (entry["layer"], entry["moves"], entry["source"], entry["unit"]
                ) == (mod.LAYER, mod.MOVES, "program_counter", "%")
        assert entry["layer"] == "L5_device"
