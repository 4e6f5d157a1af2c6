#!/usr/bin/env python3
"""A multi-token-prediction module's logits against the plain reference, on
the device, at the benchmark cell's own sizes.

    python scripts/joyai_mtp_parity.py --seeds 11 12 [--controls]

The benchmark's ``train`` driver decides ``correct`` from ``token_out``, the
MAIN model's logits (``benchmark/drivers/train.py _reference_check``): it
cannot see the module.  This builds what the driver builds, in its order —
the cell's configuration, the seeded corpus, ``Model``, ``Trainer``, the
record pipeline's first batch, ``init_state`` with the cell's ``weights_seed``
— takes the module's stream after its last norm out of the program's own
forward (a spy on ``mtp._head_loss``), makes its logits a block of positions
at a time and holds them to ``benchmark/reference/joyai_llm_flash.py
mtp_forward`` at the same widths and shapes: one ``PARITY`` JSON line a seed
with ``max|module logit - reference| / max|reference|``, the main logits'
error beside it, and both losses of program and reference; ``--controls``
adds the reference with a bfloat16 and a float8 (e4m3) residual stream.

``--rehearse-cpu`` runs the same path at the cell's toy size on the CPU.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
CELL = "train_joyai_llm_flash_ep16_s16k"
BLOCK = 2048        # positions of module logits made at a time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--controls", action="store_true")
    args = ap.parse_args()
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax, jax.numpy as jnp, numpy as np
    from benchmark.lib import cell as cell_mod, data as data_mod
    from benchmark.reference import common, joyai_llm_flash as ref
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model, mtp
    from homebrewnlp_tpu.run.train_loop import make_dataset
    from homebrewnlp_tpu.train import Trainer
    cell = cell_mod.load_cell(CELL)
    traffic, base = cell.traffic(args.rehearse_cpu), cell.model_config(args.rehearse_cpu)
    for seed in args.seeds:
        config = dict(base, data_seed=seed, model_path=f"/tmp/joyai_parity_{seed}",
                      dataset_configs=[{"path": data_mod.ensure_records(
                          int(traffic["corpus_bytes"]), int(traffic["file_tokens"]),
                          args.rehearse_cpu), "type": "text", "weight": 1}])
        params = ModelParameter(config)
        model = Model(params); trainer = Trainer(params, model)
        data = make_dataset(params)
        try:
            batch = next(iter(data))
        finally:
            data.close()
        state = trainer.init_state(batch, seed=int(cell.spec["weights_seed"]))
        variables = state.variables
        tokens = np.asarray(batch["token_x"])[..., 0]
        targets = np.asarray(batch["token_y"])[..., 0]

        def run(v, b):
            kept = {}
            real = mtp._head_loss

            def spy(p, stream, head, tgt, ahead):
                kept["stream"], kept["head"] = stream.data, head.data
                return real(p, stream, head, tgt, ahead)
            mtp._head_loss = spy
            try:
                info = model.apply(v, b, layer_stats=True)
            finally:
                mtp._head_loss = real
            return kept["stream"], kept["head"], info.layer_stats["mtp_loss"], \
                info.total_loss.data, info.token_out.data
        stream, head, mtp_loss, main_loss, token_out = jax.jit(run)(
            variables, trainer.place_batch(batch))
        logits = jax.jit(lambda x, w: jnp.einsum(
            "bshk,hkpv->bsv", x, w, preferred_element_type=jnp.float32).astype(x.dtype))
        got = np.concatenate([np.asarray(logits(stream[:, i:i + BLOCK], head).astype(jnp.float32))
                              for i in range(0, stream.shape[1], BLOCK)], axis=1)
        want = ref.mtp_forward(variables, tokens, targets, config)
        want_main = ref.forward(variables, tokens, config)
        err = lambda a, b: float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        out = {"seed": seed, "device": jax.devices()[0].device_kind,
               "module_logit_error": err(got, want),
               "module_max_reference": float(np.max(np.abs(want))),
               "main_logit_error": err(np.asarray(token_out.astype(jnp.float32))[:, :, 0], want_main),
               "program_mtp_loss": float(mtp_loss[0]),
               "reference_mtp_loss": float(ref.mtp_loss_of(want, targets, 0.0)),
               "program_main_loss": float(main_loss),
               "reference_main_loss": float(common.loss_of(want_main, targets, 0.0))}
        if args.controls:
            for name in ("bfloat16", "float8_e4m3fn"):
                low = ref.mtp_forward(variables, tokens, targets, config,
                                      stream_dtype=getattr(jnp, name))
                out[f"reference_{name}_stream_module_error"] = err(low, want)
        print("PARITY " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
