#!/usr/bin/env python3
"""What the Keye-VL-2.0 cell's ``logit_tolerance`` cannot tell apart: how many
of the kept (query, key) pairs differ between the PROGRAM (bfloat16 operands,
float32 scores and threshold, bisection) and the plain float32 reference (a
stable sort), layer by layer, and the two's index losses.

    python scripts/keye_select_control.py --workload train_keye_vl_2_0_ep8_s16k --seed <n>

The selection is discrete: a key whose score lies within the operands'
rounding of a row's 2,048th is kept by one side and dropped by the other, and
from the second layer on the two sides score slightly different streams.  This
runs ``benchmark/precision_control.py``'s set-up as it stands (the cell's
weights, the first batch of ``--seed``), the program's forward once — each
layer's choice handed to the host as it is made — and the reference's once
(``forward(kept=, losses=)``), and prints, after ``precision_control``'s own
JSON line (the program's logit error and the float8 stream's against the SAME
reference pass), one more: a layer's kept pairs, the pairs only one side
kept, the rows in which the sides differ at all, both index losses.  Exit as
``precision_control``'s.

``--rehearse-cpu`` runs the same path at the cell's toy size on the CPU
(exit 10).
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    from benchmark import precision_control
    from benchmark.drivers import train as driver
    from benchmark.lib import cell as cell_mod
    argv = list(sys.argv[1:] if argv is None else argv)
    if "--rehearse-cpu" in argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import numpy as np

    from homebrewnlp_tpu.model import indexer
    from homebrewnlp_tpu.parallel.flash_attention import unpack_keep
    chosen: dict = {}
    select_keys = indexer.select_keys

    def recording(*operands):
        layer = len(chosen)
        chosen[layer] = None
        keep = select_keys(*operands)
        jax.debug.callback(lambda words: chosen.__setitem__(
            layer, np.asarray(words)), keep)
        return keep

    report: dict = {}

    def check(ctx, config, model, trainer, mesh, state, batch):
        """The driver's comparison, from ONE pass of each side that also
        hands over the choices and the index losses."""
        tolerance = float(ctx.cell.spec["correct"]["logit_tolerance"])
        if "want" in report:
            # the lower-precision stream's logits, against the pass below
            err = float(np.max(np.abs(report["want"] - np.asarray(
                model.logits))) / np.max(np.abs(report["want"])))
            return {"logit_error": err, "logits_agree": err <= tolerance}
        ref = cell_mod.load_reference(ctx.cell.config_name)
        tokens = np.asarray(batch["token_x"])[..., 0]
        indexer.select_keys = recording
        try:
            info = jax.jit(lambda v, b: model.apply(v, b, layer_stats=True))(
                state.variables, trainer.place_batch(batch))
            got = np.asarray(info.token_out.data.astype(np.float32))[:, :, 0]
            jax.effects_barrier()
        finally:
            indexer.select_keys = select_keys
        kept, losses = [], {}
        want = np.asarray(ref.forward(state.variables, tokens, config,
                                      kept=kept, losses=losses))
        err = float(np.max(np.abs(want - got)) / np.max(np.abs(want)))
        layers = []
        for layer, theirs in enumerate(kept):
            ours = np.asarray(unpack_keep(chosen[layer]))[:, 0]
            theirs = np.unpackbits(theirs, axis=-1, bitorder="little"
                                   ).astype(bool)[..., :ours.shape[-1]]
            apart = ours != theirs
            layers.append({
                "kept_pairs": int(theirs.sum()),
                "kept_by_one_side_only": int(apart.sum()),
                "rows_that_differ": int(apart.any(axis=-1).sum()),
                "most_in_a_row": int(apart.sum(axis=-1).max()),
                "index_loss_program": float(
                    info.layer_stats["index_loss"][layer]),
                "index_loss_reference": float(losses["index"][layer])})
            ctx.log(f"layer {layer}: {layers[-1]}")
        report.update(layers=layers, want=want)
        return {"logit_error": err, "logits_agree": err <= tolerance}

    precision_control.STREAMS = ("float8_e4m3fn",)
    real = driver._reference_check
    driver._reference_check = check
    try:
        code = precision_control.main(argv)
    finally:
        driver._reference_check = real
    print(json.dumps({"layers": report.get("layers")}), flush=True)
    return code


if __name__ == "__main__":
    sys.exit(main())
