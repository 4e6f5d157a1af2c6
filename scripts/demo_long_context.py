"""Demonstrate the 1b_long_context target semantics on the 8-device CPU mesh.

Runs a width-reduced configs/1b_long_context.json — SAME sequence length
(32768), SAME sequence_parallel=8 sharding, block structure, revnet memory
strategy, and optimizer family; reduced width/depth so the demo finishes on
CPU — for a few steps and reports the losses.  Before the ring-attention
custom_vjp backward (parallel/ring_attention.py), autodiff stored the
per-hop [sq, sq] probability tensors: at the full config's shapes ~69 GB of
residuals per layer-block, which no chip holds; at THIS demo's shapes it
would still stash 8 x [1, 4, 4096, 4096] f32 = 2.1 GB per attention layer,
where the blockwise backward needs O(block_q x sq) transients.

Usage:
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python scripts/demo_long_context.py [--steps N]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    args = ap.parse_args()

    import jax
    import numpy as np

    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.core import sharding as shardlib
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.train import Trainer

    with open(os.path.join(os.path.dirname(__file__), "..",
                           "configs", "1b_long_context.json")) as f:
        cfg = json.load(f)
    # width/depth-reduced, same 32k x sp=8 shape; CPU-bf16 is slow, use f32
    cfg.update({"features_per_head": 64, "heads": 4, "depth": 2,
                "train_batch_size": 1, "vocab_size": 256,
                "calculation_dtype": "float32", "storage_dtype": "float32",
                "slice_dtype": "float32", "optimizer_slice_dtype": "float32",
                "use_checkpointing": False, "macro_batching": 1,
                "tpu_size": 8})
    params = ModelParameter(cfg)
    assert params.sequence_length == 32768
    assert params.mesh_shape.get(shardlib.SEQUENCE_AXIS) == 8
    mesh = shardlib.build_mesh(params)
    print(f"mesh: {dict(mesh.shape)} devices={len(jax.devices())}")

    rng = np.random.default_rng(0)
    x = rng.integers(0, params.vocab_size,
                     (params.train_batch_size, params.sequence_length, 1))
    batch = {"token_x": x, "token_y": (x + 1) % params.vocab_size}

    model = Model(params)
    trainer = Trainer(params, model, mesh=mesh)
    state = trainer.init_state(batch)
    n_params = sum(int(np.prod(v.shape)) for v in state.variables.values())
    print(f"params: {n_params:,}  seq={params.sequence_length} "
          f"sp={params.mesh_shape[shardlib.SEQUENCE_AXIS]}")

    losses = []
    for i in range(args.steps):
        t0 = time.monotonic()
        state, metrics = trainer.step(state, batch, jax.random.PRNGKey(i))
        loss = float(metrics["loss"])
        losses.append(loss)
        print(f"step {i}: loss={loss:.4f}  wall={time.monotonic() - t0:.1f}s",
              flush=True)
    assert all(np.isfinite(losses)), losses
    assert losses[-1] < losses[0], losses
    print("OK: 32k-sequence sp=8 training to finite, decreasing loss")


if __name__ == "__main__":
    main()
