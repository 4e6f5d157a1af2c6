"""Elastic pod training tests (marker ``elastic``; docs/DISTRIBUTED.md
'Elasticity', ROADMAP item 5 / ISSUE 14).

Two tiers:

- **Agent state machine** (device-free, injected KV/clock): lease lapse
  detection, missing-peer startup grace, coordinator loss, the
  grace-then-force-exit path with the pre-exit hook, exit-code
  classification, and the controller's jax-free checkpoint probe.
- **Controller e2e** (marked slow; real ``run_manager.py --elastic``
  subprocess fleets): SIGKILL one of 4 ranks mid-training → the survivors
  re-form at world size 3 from the freshest complete checkpoint with no
  human input and no fixed world size, grow back to 4 at a checkpoint
  boundary, and finish — with restore losses pinned against fresh
  restores and the DataLog chain proven multiset-exact across both
  membership changes.  A second e2e drives the proactive
  preemption-notice shrink (graceful 143 path).
"""
import json
import os
import re
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from multihost_test import _spawn_workers  # noqa: E402

pytestmark = pytest.mark.elastic

WORKER = os.path.join(HERE, "_elastic_train_worker.py")
RUN_MANAGER = os.path.join(HERE, "..", "scripts", "run_manager.py")


# ---- agent state machine (device-free) -------------------------------------

class _FakeKV:
    def __init__(self):
        self.store = {}
        self.fail_puts = False

    def put(self, key, value):
        if self.fail_puts:
            return False
        self.store[key] = value
        return True

    def dir_get(self, prefix):
        return [(k, v) for k, v in self.store.items()
                if k.startswith(prefix)]

    def beat(self, pid, seq, gen=0):
        self.store[f"hbnlp/elastic/g{gen}/p{pid}"] = json.dumps(
            {"seq": seq, "ospid": 1000 + pid})


def _agent(tmp_path, kv, clock, pid=0, n=3, **kw):
    from homebrewnlp_tpu.distributed.elastic import ElasticAgent
    a = ElasticAgent(str(tmp_path), pid, n, gen=0, interval_s=1.0,
                     timeout_s=5.0, exit_grace_s=1.0, kv_put=kv.put,
                     kv_dir_get=kv.dir_get, clock=lambda: clock[0],
                     exit_fn=lambda rc: None, **kw)
    a._started_at = clock[0]  # start() would stamp this; ticks are manual
    return a


def lease_lapse_detection_test(tmp_path):
    """A peer whose lease stops ADVANCING is declared lapsed after
    timeout_s on the local monotonic clock; the event names the rank, and
    the membership marker + chief lease mirror land on shared storage."""
    from homebrewnlp_tpu.distributed import elastic

    kv, clock = _FakeKV(), [0.0]
    agent = _agent(tmp_path, kv, clock)
    kv.beat(1, 1)
    kv.beat(2, 1)
    assert agent.tick() is None
    clock[0] = 3.0
    kv.beat(1, 2)  # p1 advances; p2 stalls (its age: 3s < 5s)
    assert agent.tick() is None
    # the chief mirror rides every tick
    mirror = json.load(open(elastic.lease_mirror_path(str(tmp_path))))
    assert mirror["generation"] == 0 and mirror["world_size"] == 3
    assert "1" in mirror["leases"] and "2" in mirror["leases"]
    clock[0] = 6.0
    kv.beat(1, 3)
    event = agent.tick()  # p2's last advance was t=0: age 6s > 5s
    assert event is not None and "p2" in event, event
    assert agent.lapsed == [2]
    marker = elastic.read_membership_marker(str(tmp_path), 0)
    assert marker is not None and marker["lapsed"] == [2], marker
    # sticky: later ticks cannot overwrite the first cause
    clock[0] = 9.0
    assert agent.tick() == event


def missing_peer_startup_grace_test(tmp_path):
    """A peer that NEVER published only counts as lapsed once the
    generation had timeout_s to come up — processes start their agents at
    different times (compile skew), so a missing key must not instantly
    shrink the pod."""
    kv, clock = _FakeKV(), [0.0]
    agent = _agent(tmp_path, kv, clock)
    kv.beat(1, 1)  # p2 never publishes
    assert agent.tick() is None
    clock[0] = 4.0
    kv.beat(1, 2)
    assert agent.tick() is None  # still inside the startup grace
    clock[0] = 6.0
    kv.beat(1, 3)
    event = agent.tick()
    assert event is not None and "p2" in event, event


def coordinator_loss_detection_test(tmp_path):
    """Repeated kv_put failure = the coordination service (process 0) is
    gone — a membership event blaming rank 0, not a silent retry loop."""
    kv, clock = _FakeKV(), [0.0]
    agent = _agent(tmp_path, kv, clock, pid=1)
    kv.beat(0, 1)
    kv.beat(2, 1)
    assert agent.tick() is None
    kv.fail_puts = True
    clock[0] = 2.0
    assert agent.tick() is None  # first failure only starts the window
    clock[0] = 8.0
    event = agent.tick()
    assert event is not None and "coordination service" in event, event
    assert agent.lapsed == [0]


def force_exit_grace_and_pre_exit_test(tmp_path):
    """The trigger path: grace for the main loop's own check first (a
    stop() inside the window cancels the exit), then pre_exit hook, then
    exit_fn — os._exit skips every finally, so the hook is the last
    chance for host-side accounting (the chief's DataLog flush)."""
    from homebrewnlp_tpu.distributed.elastic import (ElasticAgent,
                                                     MEMBERSHIP_EXIT_CODE)

    calls = []
    agent = ElasticAgent(str(tmp_path), 0, 2, gen=0, exit_grace_s=0.2,
                         kv_put=lambda k, v: True, kv_dir_get=lambda p: [],
                         exit_fn=lambda rc: calls.append(("exit", rc)),
                         pre_exit=lambda: calls.append(("pre", None)))
    agent.event = "test event"
    agent._trigger_exit()
    assert calls == [("pre", None), ("exit", MEMBERSHIP_EXIT_CODE)], calls

    calls.clear()
    agent2 = ElasticAgent(str(tmp_path), 0, 2, gen=0, exit_grace_s=5.0,
                          kv_put=lambda k, v: True, kv_dir_get=lambda p: [],
                          exit_fn=lambda rc: calls.append(("exit", rc)))
    agent2.event = "test event"
    agent2._stop.set()  # the main loop noticed and is exiting cleanly
    agent2._trigger_exit()
    assert calls == [], calls


def classify_exit_test():
    from homebrewnlp_tpu.distributed.elastic import classify_exit
    assert classify_exit(None) == "running"
    assert classify_exit(0) == "ok"
    assert classify_exit(143) == "preempted"
    assert classify_exit(144) == "membership"
    assert classify_exit(137) == "killed"
    assert classify_exit(-9) == "killed"
    assert classify_exit(-6) == "collateral"   # SIGABRT 'another task died'
    assert classify_exit(134) == "collateral"
    assert classify_exit(-11) == "collateral"
    assert classify_exit(-15) == "collateral"  # drain-TERMed wedged rank
    assert classify_exit(1) == "crash"


def latest_complete_step_test(tmp_path):
    """The controller's grow-boundary probe: committed ``ckpt_<step>``
    directories only — a torn ``.tmp`` save stays invisible."""
    from homebrewnlp_tpu.distributed.elastic import latest_complete_step
    assert latest_complete_step(str(tmp_path / "missing")) == -1
    assert latest_complete_step(str(tmp_path)) == -1
    for name in ("ckpt_5", "ckpt_12", "ckpt_40.tmp", "elastic", "pids"):
        os.makedirs(tmp_path / name)
    assert latest_complete_step(str(tmp_path)) == 12


# ---- controller e2e --------------------------------------------------------

def _write_records(data_dir, n_files, tokens_per_file, seed=3):
    from homebrewnlp_tpu.data.tfrecord import RecordWriter, encode_example
    os.makedirs(data_dir)
    rng = np.random.default_rng(seed)
    for i in range(n_files):
        tokens = rng.integers(0, 32, tokens_per_file).astype(np.uint8)
        with RecordWriter(str(data_dir / f"p_{i}_{tokens_per_file}"
                               ".tfrecord")) as w:
            w.write(encode_example({"text": tokens.tobytes()}))


def _elastic_cfg(tmp_path, data_dir, **over):
    cfg = {
        "model_mode": "gpt", "use_video": False, "use_language": True,
        "sequence_length": 32, "features_per_head": 8, "heads": 2,
        "depth": 1, "train_batch_size": 12, "vocab_size": 32,
        "tpu_size": 4, "calc_accuracy": False,
        "block_config": [{"layer": ["norm-shift-scale-features-group",
                                    "feed_forward-in:relu"]}],
        "memory_reduction_strategy": "none",
        "optimizer": "adam-learning_rate", "learning_rate": 1e-3,
        "weight_decay": 0.0,
        "learning_rate_config": {"linear_warmup": {"final_step": 8}},
        "mesh_shape_override": {"data": 4},
        "train_steps": 60, "use_checkpointing": True,
        "steps_per_checkpoint": 8, "checkpoint_async": True,
        "max_checkpoints_keep": 50, "interleaved_datasets": 2,
        "data_seed": 7, "storage_retry_base_delay": 0.0,
        "distributed_barrier_timeout_s": 30.0,
        "elastic_training": True, "elastic_lease_interval_s": 0.5,
        "elastic_lease_timeout_s": 8.0, "elastic_exit_grace_s": 2.0,
        "dataset_configs": [{"path": str(data_dir / "*"), "type": "text",
                             "weight": 1}],
        "model_path": str(tmp_path / "run"),
    }
    cfg.update(over)
    return cfg


def _controller_cmd(cfg_path, model_path, target, step_delay, extra=()):
    return [sys.executable, RUN_MANAGER,
            f"{sys.executable} {WORKER} {cfg_path} --step-delay "
            f"{step_delay}",
            "--model-path", str(model_path),
            "--num-processes", str(target), "--devices-per-process", "1",
            "--poll-interval", "2", "--poll-jitter", "0",
            "--stall-timeout", "0", "--term-grace", "120",
            "--max-restarts", "3", "--restart-delay", "1",
            "--elastic", *extra]


def _window_rows(ds, n_batches=None):
    """Token-x rows of the first n batches (full drain when None)."""
    out = []
    it = iter(ds)
    while n_batches is None or n_batches > 0:
        try:
            b = next(it)
        except StopIteration:
            assert n_batches is None, "stream ended early"
            break
        out.extend(bytes(row.tobytes()) for row in np.asarray(b["token_x"]))
        if n_batches is not None:
            n_batches -= 1
    return out


def _assert_datalog_multiset_exact(cfg, model_path):
    """PR 10's multiset property carried THROUGH the elastic membership
    changes: replaying every generation's DataLog entry (its own slice
    geometry, resumed through the preceding entries) and then draining
    the rest of the epoch reproduces the uninterrupted epoch exactly —
    nothing lost, nothing duplicated."""
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.data.inputs import TextDataset

    entries = [json.loads(line)
               for line in open(os.path.join(model_path, "DataLog.log"))
               if line.strip()]
    assert len(entries) >= 2, entries
    consumed = []
    for i, e in enumerate(entries):
        local = cfg["train_batch_size"] // e["slice_count"]
        for s in range(e["slice_count"]):
            ds = TextDataset(ModelParameter(dict(cfg)), local,
                             slice_index=s, slice_count=e["slice_count"],
                             runs_log=entries[:i] or None, repeat=True)
            consumed += _window_rows(ds, e["steps"])
    remainder = _window_rows(TextDataset(
        ModelParameter(dict(cfg)), cfg["train_batch_size"], slice_index=0,
        slice_count=1, runs_log=entries, repeat=False))
    reference = _window_rows(TextDataset(
        ModelParameter(dict(cfg)), cfg["train_batch_size"], slice_index=0,
        slice_count=1, repeat=False))
    assert sorted(consumed + remainder) == sorted(reference), (
        len(consumed), len(remainder), len(reference))
    return entries


@pytest.mark.slow
def elastic_shrink_grow_e2e_test(tmp_path):
    """The headline acceptance: SIGKILL one of 4 ranks mid-training.  The
    elastic controller — no human input, no fixed world size — re-forms
    the 3 survivors at a new generation resuming from the freshest
    COMPLETE checkpoint, grows back to 4 at a checkpoint boundary once
    the shrunken generation proves itself, and trains to completion.
    Pins: the resumed generation's restore forward-loss is BIT-IDENTICAL
    to a fresh 3-process restore of the same checkpoint; the re-grown
    4-process step matches a fresh 4-process restore within
    reduction-order tolerance; the DataLog chain stays multiset-exact."""
    from homebrewnlp_tpu.distributed.elastic import latest_complete_step

    data_dir = tmp_path / "data"
    _write_records(data_dir, 12, 4096)
    model_path = str(tmp_path / "run")
    cfg = _elastic_cfg(tmp_path, data_dir)
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))

    proc = subprocess.Popen(
        _controller_cmd(cfg_path, model_path, 4, 0.2,
                        extra=("--grow-delay", "3", "--elastic-drain",
                               "45")),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    killed = False
    pidfile = os.path.join(model_path, "pids", "g0_p1.pid")
    deadline = time.monotonic() + 700
    try:
        while proc.poll() is None and time.monotonic() < deadline:
            if not killed and latest_complete_step(model_path) >= 8 \
                    and os.path.exists(pidfile):
                victim = int(open(pidfile).read())
                os.kill(victim, signal.SIGKILL)
                killed = True
            time.sleep(0.5)
        assert proc.poll() is not None, "controller did not finish in time"
    finally:
        if proc.poll() is None:
            proc.kill()
    out, _ = proc.communicate(timeout=30)
    log = open(os.path.join(model_path, "run.log")).read()
    assert killed, log[-3000:]
    assert proc.returncode == 0, out[-2000:] + log[-4000:]

    # the controller's story: shrink to 3 survivors, grow back to 4, done
    assert "elastic: membership change generation 0" in log, log[-4000:]
    m = re.search(r"resuming 3 survivor\(s\) from checkpoint step (\d+)",
                  log)
    assert m, log[-4000:]
    shrink_step = int(m.group(1))
    assert "graceful grow 3 -> 4" in log, log[-4000:]
    assert "fleet finished cleanly" in log, log[-4000:]
    # the agents named the killed rank on shared storage (a survivor the
    # gloo runtime SIGABRTed on the dead rank's sockets may ride along in
    # the marker — the controller's exit census filters it back out, which
    # is exactly what the world-size-3 pins above prove)
    marker = json.load(open(os.path.join(model_path, "elastic",
                                         "membership_g0.json")))
    assert 1 in marker["lapsed"], marker

    # worker markers (pumped into run.log with [pN] prefixes)
    restores = re.findall(r"ELASTIC_RESTORE g=(\d+) world=(\d+) "
                          r"step=(\d+) fwd=(\S+)", log)
    steps_m = re.findall(r"ELASTIC_STEP g=(\d+) world=(\d+) "
                         r"step=(\d+) loss=(\S+)", log)
    shrunk = [r for r in restores if r[1] == "3"]
    assert shrunk, (restores, log[-3000:])
    g3, _, s3, fwd3 = shrunk[0]
    assert int(s3) == shrink_step, (s3, shrink_step)
    grown = [r for r in restores if r[1] == "4" and int(r[0]) > int(g3)]
    assert grown, restores
    g4, _, s4, fwd4 = grown[-1]
    loss4 = [sm[3] for sm in steps_m if sm[0] == g4 and sm[1] == "4"]
    assert loss4, steps_m
    done = re.findall(r"ELASTIC_DONE g=(\d+) world=(\d+) final_step=(\d+)",
                      log)
    assert done and done[-1][1] == "4" and done[-1][2] == "60", done

    # fresh 3-process restore of the SAME checkpoint: bit-identical
    # forward loss (single-device probe — no reduction-order excuse)
    outs3 = _spawn_workers(WORKER, [str(cfg_path), "--probe-only",
                                    "--step", s3],
                           env_devcount=1, n_procs=3, timeout=420)
    assert all(p.returncode == 0 for p, _ in outs3), \
        "\n".join(o[-2000:] for _, o in outs3)
    fresh3 = re.findall(r"ELASTIC_RESTORE_FRESH g=\d+ world=3 "
                        r"step=\d+ fwd=(\S+)",
                        "\n".join(o for _, o in outs3))
    assert fresh3 and fresh3[0] == fwd3, (fresh3, fwd3)

    # fresh 4-process restore: the re-grown step within reduction-order
    # tolerance (and the restored bytes themselves still bit-identical)
    outs4 = _spawn_workers(WORKER, [str(cfg_path), "--probe-only",
                                    "--step", s4],
                           env_devcount=1, n_procs=4, timeout=420)
    assert all(p.returncode == 0 for p, _ in outs4), \
        "\n".join(o[-2000:] for _, o in outs4)
    joined = "\n".join(o for _, o in outs4)
    fresh4_fwd = re.findall(r"ELASTIC_RESTORE_FRESH g=\d+ world=4 "
                            r"step=\d+ fwd=(\S+)", joined)
    fresh4_loss = re.findall(r"ELASTIC_STEP_FRESH g=\d+ world=4 "
                             r"step=\d+ loss=(\S+)", joined)
    assert fresh4_fwd and fresh4_fwd[0] == fwd4, (fresh4_fwd, fwd4)
    assert fresh4_loss, joined[-2000:]
    np.testing.assert_allclose(float(loss4[0]), float(fresh4_loss[0]),
                               rtol=1e-5)

    # data-stream accounting across BOTH membership changes
    entries = _assert_datalog_multiset_exact(cfg, model_path)
    counts = [e["slice_count"] for e in entries]
    assert counts[0] == 4 and 3 in counts and counts[-1] == 4, counts


@pytest.mark.slow
def preempt_notice_graceful_shrink_test(tmp_path):
    """The PROACTIVE path: cloud tooling announces an upcoming capacity
    loss by writing ``elastic/preempt.json``; the controller shrinks
    through the graceful 143 rotation (pod-wide SIGTERM → emergency
    checkpoint → relaunch smaller) — no steps lost, notice cleared, and
    the ``hbnlp_elastic_*`` gauges visible in the run's telemetry."""
    data_dir = tmp_path / "data"
    _write_records(data_dir, 8, 4096, seed=5)
    model_path = str(tmp_path / "run")
    cfg = _elastic_cfg(
        tmp_path, data_dir, train_batch_size=8, tpu_size=2,
        mesh_shape_override={"data": 2}, train_steps=40,
        steps_per_checkpoint=6, telemetry_enabled=True,
        telemetry_jsonl_interval_s=0.05)
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))

    proc = subprocess.Popen(
        _controller_cmd(cfg_path, model_path, 2, 0.25,
                        extra=("--grow-delay", "100000",)),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    noticed = False
    deadline = time.monotonic() + 500
    try:
        while proc.poll() is None and time.monotonic() < deadline:
            if not noticed and os.path.exists(
                    os.path.join(model_path, "metrics.jsonl")):
                os.makedirs(os.path.join(model_path, "elastic"),
                            exist_ok=True)
                with open(os.path.join(model_path, "elastic",
                                       "preempt.json"), "w") as f:
                    json.dump({"count": 1}, f)
                noticed = True
            time.sleep(0.5)
        assert proc.poll() is not None, "controller did not finish in time"
    finally:
        if proc.poll() is None:
            proc.kill()
    out, _ = proc.communicate(timeout=30)
    log = open(os.path.join(model_path, "run.log")).read()
    assert noticed and proc.returncode == 0, out[-2000:] + log[-4000:]
    assert "elastic: preemption notice" in log, log[-4000:]
    assert "graceful shrink 2 -> 1" in log, log[-4000:]
    assert "fleet finished cleanly" in log, log[-4000:]
    # the notice was consumed, not left to re-trigger forever
    assert not os.path.exists(os.path.join(model_path, "elastic",
                                           "preempt.json"))
    # graceful = the 143 path: gen 0 wrote its emergency checkpoint and
    # gen 1 finished the full run single-process
    done = re.findall(r"ELASTIC_DONE g=(\d+) world=(\d+) final_step=(\d+)",
                      log)
    assert done and done[-1][1] == "1" and done[-1][2] == "40", done
    # elastic observability rode the normal telemetry pipeline (world 2)
    tele = open(os.path.join(model_path, "telemetry.jsonl")).read()
    assert "hbnlp_elastic_generation" in tele
    assert "hbnlp_elastic_world_size" in tele
    entries = _assert_datalog_multiset_exact(cfg, model_path)
    assert [e["slice_count"] for e in entries] == [2, 1], entries
