"""Multi-host input assembly (VERDICT round-1 missing #1).

Spawns TWO real jax processes (multi-controller, CPU, 4 virtual devices
each) and verifies shard_batch assembles distinct per-process dataset slices
into one global sharded batch via jax.make_array_from_process_local_data —
the rebuild's equivalent of the reference's per-host infeed placement
(/root/reference/src/run/dataloader_placement.py:153-227).
"""
import os
import re
import subprocess
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port() -> int:
    from homebrewnlp_tpu.distributed.bootstrap import free_port
    return free_port()



STARVATION_RCS = (-6, 134)  # gloo SIGABRT: 'another task died'


def starvation_retry_reason(rcs, outs=()):
    """Classify a failed fleet attempt: returns the retry-reason line to
    stamp into the test log when the exit-code shape matches the known
    1-core scheduler-starvation flake (the coordination-service heartbeat
    starves, so gloo SIGABRTs the fleet with 'another task died'), else
    None — an unclassified failure is a real regression and the caller
    decides whether to retry.  Shared by _spawn_workers and the direct
    fleet call sites that need their own spawn loop (forensics_test's
    SIGKILL e2e) so the retry policy and its logging cannot drift between
    copies."""
    if not any(rc in STARVATION_RCS for rc in rcs):
        return None
    marker = any("another task died" in (o or "") for o in outs)
    return (f"worker rcs={rcs} — heartbeat starvation (SIGABRT -6 = "
            "'another task died'"
            + ("; marker seen in worker output" if marker else "")
            + "; 1-core scheduler contention, not product behavior)")


def _spawn_workers(worker: str, extra_args, env_devcount: int = 4,
                   n_procs: int = 2, timeout: int = 420, retries: int = 1):
    """Launch n multi-controller worker processes on a shared coordinator
    port with a virtual CPU mesh; returns [(proc, output), ...].

    This is THE shared fleet-spawning helper for every multi-process test
    path (multihost, distributed, elastic suites): it owns the one
    contention-flake retry, so the policy and its logging cannot drift
    between copies.  A 1-core CI box oversubscribed by N jax processes
    occasionally starves the coordination-service heartbeat, which SIGABRTs
    the entire fleet with 'another task died' — scheduler starvation, not
    product behavior.  Under tier-1 contention this was the one remaining
    flake (every suite passes standalone); the whole fleet retries once and
    correctness assertions run on the surviving attempt's output.  Each
    retry logs WHY (per-worker exit codes + the first failing worker's
    tail) so a starvation retry is distinguishable from a real regression
    in the test log."""
    last = None
    for attempt in range(retries + 1):
        port = _free_port()
        env = dict(os.environ)
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                       env.get("XLA_FLAGS", ""))
        env.update(JAX_PLATFORMS="cpu",
                   XLA_FLAGS=flags +
                   f" --xla_force_host_platform_device_count={env_devcount}")
        procs = [subprocess.Popen(
            [sys.executable, worker, str(port), str(pid), str(n_procs)]
            + [str(a) for a in extra_args],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
            for pid in range(n_procs)]
        results = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout)
            except subprocess.TimeoutExpired:
                for q in procs:
                    q.kill()
                raise
            results.append((p, out))
        if all(p.returncode == 0 for p, _ in results):
            return results
        last = results
        if attempt < retries:
            rcs = [p.returncode for p, _ in results]
            outs = [out for _, out in results]
            reason = starvation_retry_reason(rcs, outs) or (
                f"worker rcs={rcs} (unclassified — single-core heartbeat "
                "starvation is still the most likely cause under tier-1 "
                "contention)")
            first_bad = next(out for p, out in results if p.returncode)
            print(f"FLEET RETRY {attempt + 1}/{retries}: {reason}.  "
                  f"First failing worker tail:\n{first_bad[-600:]}",
                  flush=True)
    return last


def two_process_assembly_test():
    results = _spawn_workers(os.path.join(HERE, "_multihost_worker.py"), [],
                             timeout=300)
    for pid, (p, out) in enumerate(results):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"worker {pid}: OK" in out, out


def four_process_assembly_test():
    """4 controllers x 4 virtual devices = a 16-device pod: the per-process
    slice layout and cross-process gather must hold beyond the 2-process
    case (process-group derivation at wider DCN fan-out)."""
    results = _spawn_workers(os.path.join(HERE, "_multihost_worker.py"), [],
                             n_procs=4, timeout=300)
    for pid, (p, out) in enumerate(results):
        assert p.returncode == 0, f"worker {pid} failed:\n{out}"
        assert f"worker {pid}: OK" in out, out


def single_process_macro_axis_test():
    """shard_batch shards the batch axis (axis 1 under macro-batching), never
    the macro axis."""
    import jax
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.core import sharding as shardlib

    cfg = {"model_mode": "gpt", "use_video": False, "use_language": True,
           "sequence_length": 16, "features_per_head": 8, "heads": 2,
           "depth": 1, "train_batch_size": 8, "vocab_size": 256,
           "tpu_size": 8, "macro_batching": 2,
           "mesh_shape_override": {"data": 8},
           "model_path": "/tmp/macro_axis_run"}
    params = ModelParameter(cfg)
    mesh = shardlib.build_mesh(params)
    batch = {"token_x": np.zeros((2, 8, 16, 1), np.int32)}
    out = shardlib.shard_batch(params, batch, mesh)["token_x"]
    spec = out.sharding.spec
    assert len(spec) >= 2 and spec[0] is None and spec[1] == "data", spec


def two_process_train_loop_test(tmp_path):
    """The REAL train loop over two jax processes: per-process dataset
    slices, global-batch assembly, chief-only artifact writes, identical
    loss trajectory on both controllers."""
    import json

    from homebrewnlp_tpu.data.tfrecord import RecordWriter, encode_example

    data_dir = tmp_path / "data"
    os.makedirs(data_dir)
    rng = np.random.default_rng(0)
    for i in range(4):  # >= 2 files per process slice
        base = np.tile(np.arange(32, dtype=np.uint8), 4096 // 32)
        noise = rng.integers(0, 32, 4096).astype(np.uint8)
        tokens = np.where(rng.random(4096) < 0.05, noise, base)
        with RecordWriter(str(data_dir / f"p_{i}_4096.tfrecord")) as w:
            w.write(encode_example({"text": tokens.tobytes()}))

    cfg = {
        "model_mode": "gpt", "use_video": False, "use_language": True,
        "sequence_length": 32, "features_per_head": 16, "heads": 2,
        "depth": 2, "train_batch_size": 8, "vocab_size": 32,
        "calc_accuracy": False, "memory_reduction_strategy": "revnet",
        "block_config": [{"layer": ["norm-shift-scale-features-group",
                                    "feed_forward-in:relu"]}],
        "group_linear_factor": 2, "tpu_size": 8,
        "mesh_shape_override": {"data": 8},
        "optimizer": "adam-learning_rate", "learning_rate": 0.003,
        "weight_decay": 0.0,
        "learning_rate_config": {"linear_warmup": {"final_step": 8}},
        "train_steps": 12, "interleaved_datasets": 2,
        "use_checkpointing": True, "steps_per_checkpoint": 10,
        "data_seed": 7,
        "dataset_configs": [{"path": str(data_dir / "*"), "type": "text",
                             "weight": 1}],
        "model_path": str(tmp_path / "run"),
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))

    results = _spawn_workers(os.path.join(HERE, "_multihost_train_worker.py"),
                             [cfg_path])
    finals = []
    for pid, (p, out) in enumerate(results):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-4000:]}"
        line = [l for l in out.splitlines() if l.startswith(f"WORKER {pid}")]
        assert line, out
        finals.append(float(line[0].split("FINAL")[1].split()[0]))
    # both controllers ran the same global computation
    assert finals[0] == finals[1], finals
    # chief-only artifacts: one metrics file, checkpoints exist, and no
    # duplicate-writer corruption in the jsonl
    run_dir = tmp_path / "run"
    metrics = [json.loads(l) for l in open(run_dir / "metrics.jsonl")]
    assert metrics and all(np.isfinite(m["loss"]) for m in metrics)
    assert any(d.startswith("ckpt_") for d in os.listdir(run_dir))


def two_process_model_sharded_checkpoint_test(tmp_path):
    """Model-axis sharding ACROSS processes (mesh model=8 over 2 controllers,
    the v5p full-model-parallel shape): the train loop runs, and a
    distributed checkpoint writes each process's owned shards which restore()
    reassembles bit-exact against the allgathered live values."""
    import json

    from homebrewnlp_tpu.data.tfrecord import RecordWriter, encode_example

    data_dir = tmp_path / "data"
    os.makedirs(data_dir)
    rng = np.random.default_rng(1)
    for i in range(4):
        tokens = rng.integers(0, 32, 4096).astype(np.uint8)
        with RecordWriter(str(data_dir / f"p_{i}_4096.tfrecord")) as w:
            w.write(encode_example({"text": tokens.tobytes()}))

    cfg = {
        "model_mode": "gpt", "use_video": False, "use_language": True,
        "sequence_length": 32, "features_per_head": 16, "heads": 8,
        "depth": 1, "train_batch_size": 8, "vocab_size": 32,
        "calc_accuracy": False, "memory_reduction_strategy": "none",
        "block_config": [{"layer": ["norm-shift-scale-features-group",
                                    "feed_forward-in:relu"]}],
        "group_linear_factor": 2, "tpu_size": 8,
        "mesh_shape_override": {"data": 1, "model": 8},
        "optimizer": "adam-learning_rate", "learning_rate": 0.003,
        "weight_decay": 0.0,
        "learning_rate_config": {"linear_warmup": {"final_step": 8}},
        "train_steps": 4, "interleaved_datasets": 2,
        "use_checkpointing": False, "data_seed": 11,
        "dataset_configs": [{"path": str(data_dir / "*"), "type": "text",
                             "weight": 1}],
        "model_path": str(tmp_path / "run"),
    }
    cfg_path = tmp_path / "cfg.json"
    cfg_path.write_text(json.dumps(cfg))

    results = _spawn_workers(os.path.join(HERE, "_multihost_train_worker.py"),
                             [cfg_path])
    losses = []
    for pid, (p, out) in enumerate(results):
        assert p.returncode == 0, f"worker {pid} failed:\n{out[-4000:]}"
        assert f"WORKER {pid} DISTCKPT OK" in out, out[-2000:]
        line = [l for l in out.splitlines()
                if l.startswith(f"WORKER {pid} DISTRESUME OK")]
        assert line, out[-2000:]
        losses.append(float(line[0].rsplit(None, 1)[1]))
    # the post-restore step computes the same global loss on both controllers
    assert losses[0] == losses[1], losses
