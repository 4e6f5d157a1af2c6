#!/usr/bin/env python3
"""A looped model's step against its plain reference, on the device, at a
benchmark cell's own sizes.

    python scripts/ouro_parity.py --workload train_ouro_2_6b_loop4_s4k --seeds 1 2 3

The benchmark's ``train`` driver decides ``correct`` from the LAST pass's
logits and compares the step's loss with that pass's cross-entropy alone
(``benchmark/drivers/train.py _reference_check``): it cannot see the other
passes, the exit distribution or the gated loss.  This builds what the driver
builds, in its order — the cell's configuration, the seeded corpus, ``Model``,
``Trainer``, the record pipeline's first batch, ``init_state`` — and holds
``Model.apply`` on that batch to ``benchmark/reference/ouro_2_6b.py`` (float32,
``highest``, a sequence at a time) in what the step trains on: every pass's
mean cross-entropy ``CE_t``, the mean exit share ``p_t``, the mean entropy and
the loss.  One JSON line a seed and a final ``{"ok": ...}``; exit 1 where a
number is further off than its bound.

``--rehearse-cpu`` runs the same path at the cell's toy size on the CPU.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: the passes' mean cross-entropies and the loss: float32 sums over bfloat16
#: logits whose largest entry is off by up to the cell's ``logit_tolerance``
#: (2^-4 of ~1.5 at the seeded weights); a mean over 8,192 tokens of
#: differences of logits moves by far less.  2^-6 holds a wrong weighting of
#: the passes (the zero gate's (1/2, 1/4, 1/8, 1/8) against uniform moves the
#: loss by the spread of the CE_t) only where the passes differ, which is
#: what the bound on p is for
LOSS_TOLERANCE = 2.0 ** -6
#: the mean exit shares and the entropy: the gate is a float32 dot of a
#: bfloat16 stream with unit entries and 2,048 weights at 0.02: its logit
#: moves by ~0.9 x 2^-8, a share by a quarter of that
SHARE_TOLERANCE = 2.0 ** -7


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.lib import cell as cell_mod, data as data_mod
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.run.train_loop import make_dataset
    from homebrewnlp_tpu.train import Trainer
    cell = cell_mod.load_cell(args.workload)
    if not args.rehearse_cpu and jax.devices()[0].platform != "tpu":
        print("ouro_parity.py: needs a TPU (or --rehearse-cpu)",
              file=sys.stderr)
        return 3
    ref = cell_mod.load_reference(cell.config_name)
    traffic = cell.traffic(args.rehearse_cpu)
    ok = True
    for seed in args.seeds:
        config = cell.model_config(args.rehearse_cpu)
        config.update(
            data_seed=int(seed),
            model_path=os.path.join(cell_mod.out_dir(
                cell.name + ".parity", args.rehearse_cpu), "run"),
            dataset_configs=[{"path": data_mod.ensure_records(
                int(traffic["corpus_bytes"]), int(traffic["file_tokens"]),
                args.rehearse_cpu), "type": "text", "weight": 1}])
        params = ModelParameter(config)
        model = Model(params)
        trainer = Trainer(params, model)
        data = make_dataset(params)
        try:
            batch = next(iter(data))
        finally:
            data.close()
        state = trainer.init_state(batch)

        def forward(variables, placed):
            info = model.apply(variables, placed, layer_stats=True)
            return info.total_loss.data, info.layer_stats
        loss, stats = jax.device_get(jax.jit(forward)(
            state.variables, trainer.place_batch(batch)))
        tokens = np.asarray(batch["token_x"])[..., 0]
        targets = np.asarray(batch["token_y"])[..., 0]
        rows = [jax.device_get({k: v for k, v in ref.outputs(
            state.variables, tokens[i:i + 1], targets[i:i + 1], config,
            keep_logits=False).items() if k != "logits"})
            for i in range(len(tokens))]
        cross = np.mean([np.mean(r["token_loss"], axis=(1, 2)) for r in rows],
                        axis=0)
        share = np.mean([np.mean(r["p"], axis=(1, 2)) for r in rows], axis=0)
        entropy = float(np.mean([np.mean(r["entropy"]) for r in rows]))
        want_loss = float(np.mean([r["loss"] for r in rows]))
        line = {
            "seed": seed, "loss": [float(loss), want_loss],
            "pass_loss": [np.asarray(stats["loop_pass_loss"]).tolist(),
                          cross.tolist()],
            "exit_share": [np.asarray(stats["loop_exit_share"]).tolist(),
                           share.tolist()],
            "exit_entropy": [float(stats["loop_exit_entropy"][0]), entropy]}
        line["loss_error"] = max(
            abs(line["loss"][0] - line["loss"][1]),
            float(np.max(np.abs(np.subtract(*line["pass_loss"])))))
        line["share_error"] = max(
            float(np.max(np.abs(np.subtract(*line["exit_share"])))),
            abs(line["exit_entropy"][0] - line["exit_entropy"][1]))
        line["agrees"] = bool(line["loss_error"] <= LOSS_TOLERANCE
                              and line["share_error"] <= SHARE_TOLERANCE)
        ok = ok and line["agrees"]
        print(json.dumps(line), flush=True)
        del state, trainer, model
    print(json.dumps({"ok": ok, "loss_tolerance": LOSS_TOLERANCE,
                      "share_tolerance": SHARE_TOLERANCE}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
