"""Bring-up guards (PR 21): what must hold for the program to start on the
chip, checked here where there is none.

* ``chip_smoke.py`` without a TPU exits non-zero within seconds, runs no leg
  and prints no result; alone in a directory it refuses likewise; its
  parent logic (config derivation, seeded corpus, child sequencing, exit
  codes, the shared compile cache) runs under the explicit CPU rehearsal,
  which can never pass;
* no path moves itself to the CPU: ``dryrun_multichip`` fails when the
  devices it needs are absent;
* one process per chip: the router parent starts no jax backend, and the
  replica fleet binds replicas to chips or refuses;
* what steers the program says where it came from: an unknown device kind
  is an error off the CPU, a mesh may leave no device idle, the native
  reader is rebuilt from its source and reports g++'s own error;
* one train-step executable per run: a resumed state lowers to the program
  the first run cached, and under a mesh the second step does not recompile.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (jax-free by contract; asserted below)


def _cpu_env(**extra):
    return dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO, **extra)


# ---- chip_smoke.py ----------------------------------------------------------

def smoke_without_tpu_runs_nothing_test(tmp_path):
    t0 = time.monotonic()
    res = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=120, cwd=tmp_path)
    assert res.returncode not in (0, chip_smoke.REHEARSAL_EXIT)
    assert time.monotonic() - t0 < 60
    assert "no TPU" in res.stderr and "nothing was run" in res.stderr
    assert res.stdout == ""  # no leg line, no result line


def smoke_alone_in_a_directory_refuses_test(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    res = subprocess.run([sys.executable, "chip_smoke.py", "--rehearse-cpu"],
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=60, cwd=tmp_path)
    assert res.returncode == 2 and res.stdout == ""
    assert "not a checkout" in res.stderr
    assert os.listdir(tmp_path) == ["chip_smoke.py"]


def smoke_config_changes_only_the_deployment_share_test():
    with open(os.path.join(REPO, "configs", "32big_mixer.json")) as f:
        shipped = json.load(f)
    allowed = {"train_batch_size", "tpu_size", "train_steps", "model_path",
               "steps_per_checkpoint", "dataset_configs", "save_graph",
               "serve_engine", "serve_request_deadline_s"}
    one, one_resume = chip_smoke.derive_configs(1, rehearsal=False)
    assert {k for k in one if one[k] != shipped.get(k)} <= allowed
    assert (one["train_batch_size"], one["tpu_size"]) == (32, 1)
    assert (one["features_per_head"], one["heads"], one["depth"],
            one["sequence_length"]) == (512, 8, 32, 512)
    assert one_resume["train_steps"] == 2 * one["train_steps"]
    assert {k for k in one if one[k] != one_resume[k]} == {"train_steps"}
    four, _ = chip_smoke.derive_configs(4, rehearsal=False)
    assert {k for k in four if four[k] != shipped.get(k)} \
        <= allowed | {"mesh_shape_override"}
    assert four["mesh_shape_override"] == {"data": 2, "model": 2}
    assert (four["train_batch_size"], four["tpu_size"]) == (64, 4)
    toy, _ = chip_smoke.derive_configs(1, rehearsal=True)
    assert toy["depth"] < one["depth"]


def smoke_corpus_comes_from_the_seed_test(tmp_path):
    paths = [str(tmp_path / n) for n in "abc"]
    chip_smoke.write_corpus(paths[0], seed=1, size=1 << 16)
    chip_smoke.write_corpus(paths[1], seed=1, size=1 << 16)
    chip_smoke.write_corpus(paths[2], seed=2, size=1 << 16)
    a, b, c = (open(p, "rb").read() for p in paths)
    assert a == b and a != c and len(a) >= 1 << 16
    assert max(a) < 128  # plain ascii for a vocab-256 char model


def smoke_rehearsal_runs_every_leg_and_cannot_pass_test(tmp_path):
    """The whole parent on the CPU at a toy size: four children in sequence
    sharing the compile cache the ENVIRONMENT named, the resume adding no
    train-step executable, the server drained — and still not a pass."""
    cache = tmp_path / "cache"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--rehearse-cpu"],
        env=_cpu_env(JAX_COMPILATION_CACHE_DIR=str(cache),
                     XLA_FLAGS="--xla_force_host_platform_device_count=1"),
        capture_output=True, text=True, timeout=800, cwd=REPO)
    assert res.returncode == chip_smoke.REHEARSAL_EXIT, \
        res.stdout[-3000:] + res.stderr[-2000:]
    assert "PASS" not in res.stdout and '"ok"' not in res.stdout
    assert res.stdout.rstrip().endswith("this is not a pass")
    with open(os.path.join(chip_smoke.REPORT_DIR, "report.json")) as f:
        report = json.load(f)
    assert report["rehearsal"] and not report["failed"]
    assert report["device"]["cache_dir"] == str(cache)
    legs = report["legs"]
    assert [legs[n]["status"] for n, _ in chip_smoke.LEGS] == ["ok"] * 4
    assert legs["train"]["train_step_cache_entries"] == [0, 1]
    assert legs["resume"]["train_step_cache_entries"] == [1, 1]
    assert legs["resume"]["child_compile_s"] is not None
    assert legs["serve"]["engine"]["mode"] == "continuous"
    assert legs["serve"]["max_in_flight"] >= 3
    assert any(f.endswith("-cache") for f in os.listdir(cache))


def smoke_module_is_jax_free_test():
    res = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke; assert 'jax' not in sys.modules"],
        cwd=REPO, capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr


# ---- no path moves itself to the CPU ---------------------------------------

def dryrun_multichip_raises_without_the_devices_test():
    res = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__ as g; g.dryrun_multichip(4)"],
        cwd=REPO, capture_output=True, text=True, timeout=120,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert res.returncode != 0
    assert "needs 4 devices, this process has 1" in res.stderr
    assert "dryrun_multichip(4):" not in res.stdout  # no leg ran anywhere


# ---- one process per chip ---------------------------------------------------

def router_parent_starts_no_backend_test():
    """serve_replicated's set-up, with the modules main.py has imported by
    then and a device-free stand-in for the replicas, must leave jax's
    backend table empty: the parent that touches the chip takes it from
    the replica that needs it."""
    code = f"""
import functools, socket, sys, threading, time
sys.path.insert(0, {TESTS!r})
import router_test
import homebrewnlp_tpu.run.modes  # what main.py has imported (incl. jax)
from homebrewnlp_tpu.distributed import replica_fleet
from homebrewnlp_tpu.infer import router
replica_fleet.ReplicaFleet = functools.partial(
    replica_fleet.ReplicaFleet, target=router_test._stub_replica_ok)

class P:
    serve_replicas = 2
    _raw_config = {{}}

with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
stop, control = threading.Event(), {{}}
t = threading.Thread(target=router.serve_replicated, args=(P(),),
                     kwargs=dict(port=port, stop=stop, control=control))
t.start()
deadline = time.monotonic() + 60
while not (control.get("fleet") and control["fleet"].alive() == 2):
    assert time.monotonic() < deadline and t.is_alive()
    time.sleep(0.1)
from jax._src import xla_bridge
backends = dict(xla_bridge._backends)
stop.set()
t.join(60)
assert not t.is_alive()
assert not backends, backends
"""
    res = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         env=_cpu_env(), capture_output=True, text=True,
                         timeout=240)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]


def replica_fleet_binds_chips_or_refuses_test(monkeypatch):
    from homebrewnlp_tpu.distributed import replica_fleet as rf

    assert rf.local_tpu_chips() == []  # JAX_PLATFORMS=cpu: no chips claimed
    monkeypatch.setattr(rf, "local_tpu_chips", lambda: ["0", "1", "2", "3"])
    with pytest.raises(rf.ReplicaChipError, match="5 replicas .* 4 TPU chip"):
        rf.ReplicaFleet({}, 5, base_port=0)
    fleet = rf.ReplicaFleet({}, 3, base_port=0)
    assert fleet._chips == ["0", "1", "2"]
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "7")
    with rf._bound_to_chip("2"):
        assert os.environ["TPU_VISIBLE_CHIPS"] == "2"
        assert os.environ["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert os.environ["TPU_VISIBLE_CHIPS"] == "7"
    assert "TPU_PROCESS_BOUNDS" not in os.environ


# ---- what steers the program says where it came from ------------------------

class _FakeDevice:
    def __init__(self, platform, kind, stats=None):
        self.platform, self.device_kind, self._stats = platform, kind, stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def unknown_device_kind_is_an_error_off_the_cpu_test():
    from homebrewnlp_tpu.utils import flops

    v5e = _FakeDevice("tpu", "TPU v5 lite",
                      {"bytes_limit": 16909336064, "bytes_in_use": 1})
    assert flops.peak_flops(v5e) == 197e12
    assert flops.hbm_capacity(v5e) == (16909336064, "memory_stats")
    aot = _FakeDevice("tpu", "TPU v5 lite", RuntimeError("no client"))
    assert flops.hbm_capacity(aot) == (flops.HBM_BYTES["TPU v5 lite"],
                                       "table:TPU v5 lite")
    unknown = _FakeDevice("tpu", "TPU v99")
    for lookup in (flops.peak_flops, flops.peak_hbm_bandwidth,
                   flops.device_hbm_bytes):
        with pytest.raises(flops.UnknownDeviceKindError, match="TPU v99"):
            lookup(unknown)
    cpu = _FakeDevice("cpu", "some host cpu")
    assert flops.peak_flops(cpu) == flops.PEAK_TFLOPS["cpu"]
    assert flops.hbm_capacity(cpu) == (flops.HBM_BYTES["cpu"], "table:cpu")


def mesh_may_leave_no_device_idle_test():
    import jax
    from backend import make_params
    from homebrewnlp_tpu.core import sharding as shardlib

    params = make_params(heads=2, tpu_size=8,
                         mesh_shape_override={"data": 4, "model": 2})
    mesh = shardlib.build_mesh(params, jax.devices()[:8])
    assert dict(mesh.shape) == {"data": 4, "model": 2}
    with pytest.raises(ValueError, match="covers 4 of 6 devices"):
        shardlib.build_mesh(make_params(
            heads=4, tpu_size=8, mesh_shape_override={"data": 2, "model": 4}),
            jax.devices()[:6])
    on_four = {"w": jax.device_put(np.zeros((4, 4), np.float32),
                                   jax.sharding.NamedSharding(
        shardlib.build_mesh(params, jax.devices()[:8]),
        jax.sharding.PartitionSpec()))}
    assert "8/8 local devices" in shardlib.placement_report(on_four, mesh)
    on_one = {"w": jax.numpy.zeros((4, 4))}
    with pytest.raises(RuntimeError, match="hold no parameters"):
        shardlib.placement_report(on_one, None)


def native_library_is_keyed_on_its_source_test(tmp_path, monkeypatch, capsys):
    from homebrewnlp_tpu.data import _native

    monkeypatch.setattr(_native, "NATIVE_DIR", str(tmp_path))
    monkeypatch.setattr(_native, "_cache", {})
    monkeypatch.setattr(_native, "load_errors", {})
    src = tmp_path / "tiny.cpp"
    src.write_text('extern "C" int answer() { return 41; }\n')
    (tmp_path / "libtiny.so").write_bytes(b"a binary from another machine")

    def declare(lib):
        lib.answer.restype = int

    lib = _native.load_library("tiny", declare)
    assert lib.answer() == 41
    first = _native.library_path("tiny")
    assert os.listdir(tmp_path).count(os.path.basename(first)) == 1
    assert not (tmp_path / "libtiny.so").exists()  # foreign binary dropped
    # edited source: a new key, rebuilt here, the stale binary removed
    src.write_text('extern "C" int answer() { return 42; }\n')
    _native._cache.clear()
    assert _native.library_path("tiny") != first
    assert _native.load_library("tiny", declare).answer() == 42
    assert not os.path.exists(first)
    # a build failure is said out loud, with g++'s own words
    src.write_text("this is not c++\n")
    _native._cache.clear()
    assert _native.load_library("tiny", declare) is None
    assert "error" in _native.load_errors["tiny"]
    assert "native tiny unavailable" in capsys.readouterr().out


# ---- one train-step executable per run --------------------------------------

def _trainer(mesh_devices=None):
    import jax
    from backend import make_params
    from homebrewnlp_tpu.core import sharding as shardlib
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.train import Trainer

    params = make_params(
        heads=2, tpu_size=4, train_batch_size=4,
        mesh_shape_override={"data": 2, "model": 2},
        optimizer="adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate")
    mesh = shardlib.build_mesh(params, jax.devices()[:mesh_devices]) \
        if mesh_devices else None
    trainer = Trainer(params, Model(params), mesh=mesh)
    x = np.random.default_rng(0).integers(
        0, params.vocab_size, (4, params.sequence_length, 1))
    batch = {"token_x": x, "token_y": (x + 1) % params.vocab_size}
    return trainer, trainer.init_state(batch), batch


def second_step_under_a_mesh_does_not_recompile_test():
    import jax

    trainer, state, batch = _trainer(mesh_devices=4)
    before = [v.sharding for v in jax.tree_util.tree_leaves(state)]
    for _ in range(3):
        state, metrics = trainer.step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    after = [v.sharding for v in jax.tree_util.tree_leaves(state)]
    assert before == after  # the state comes back laid out as it went in
    assert trainer._step_fn._cache_size() == 1


def restored_single_device_state_lowers_like_a_fresh_one_test():
    import jax
    from homebrewnlp_tpu.core import sharding as shardlib

    trainer, state, batch = _trainer()
    host = jax.tree_util.tree_map(np.asarray, state)
    restored = type(state)(*(shardlib.place_tree(t, h)
                             for t, h in zip(state, host)))
    assert trainer.lowered(state, batch).as_text() \
        == trainer.lowered(restored, batch).as_text()
