#!/usr/bin/env python3
"""The control behind a training cell's ``logit_tolerance``: a lower
precision than the configuration states has to come out as NOT correct.

    python benchmark/precision_control.py --workload <name> --seed <n>

Builds what the ``train`` driver builds, in its order — the cell's
configuration, the seeded corpus, ``Model``, ``Trainer``, the record
pipeline's first batch, ``init_state`` with the cell file's ``weights_seed``
(so the logits are of the weights every run of the cell trains; ``--seed``
deals out the first batch only, as in a run, and ``--weights-seed`` reads
another draw of the weights, as the tolerances' readings before PR 57 did
with every seed) — and puts three sets of logits through
the driver's OWN comparison (``drivers/train.py _reference_check``, the
cell's ``logit_tolerance``) against the plain float32 reference: the
program's, as every run of the cell does, and the reference's with its
residual stream rounded to bfloat16 and to float8 (e4m3) after every block
(``forward(..., stream_dtype=)``).  The last line printed is one JSON object
with the driver's numbers for each; the exit code is 0 only where the program
agrees and the float8 stream does not, so the limit lies between the two.

``--rehearse-cpu`` runs the same path at the cell's toy size on the CPU,
where the limit, set on the chip at the published widths, need not separate
anything: it exercises this file and exits 10.
"""
import argparse
import json
import os
import sys
import time
import types

T_START = time.monotonic()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

EXIT_NOT_SEPARATED = 1
EXIT_NO_ACCELERATOR = 3
EXIT_REHEARSAL = 10
STREAMS = ("bfloat16", "float8_e4m3fn")


class _Logits:
    """Stands where ``_reference_check`` expects the trainer and the model:
    the "placed batch" is a set of logits, and the forward hands them back in
    the program's layout ``[batch, sequence, 1, vocabulary]``."""

    def __init__(self, logits):
        self.logits = logits

    def place_batch(self, _batch):
        return {"logits": self.logits}

    def apply(self, _variables, placed, mesh=None):
        return types.SimpleNamespace(token_out=types.SimpleNamespace(
            data=placed["logits"][:, :, None, :]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--weights-seed", type=int, default=None,
                    help="another draw of the weights than the cell's own")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    from benchmark.lib import cell as cell_mod, data as data_mod
    from benchmark.lib.result import Context
    cell = cell_mod.load_cell(args.workload)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp
    import numpy as np
    if not args.rehearse_cpu and (jax.devices()[0].platform != "tpu"
                                  or len(jax.devices()) != cell.chips):
        print(f"precision_control.py: cell {cell.name} needs {cell.chips} "
              f"TPU chip(s)", file=sys.stderr)
        return EXIT_NO_ACCELERATOR
    if cell.chips != 1:
        raise SystemExit("precision_control.py: one-chip cells only")

    from benchmark.drivers.train import (_reference_check, seeds_line,
                                         weights_seed_and_origin)
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.run.train_loop import make_dataset
    from homebrewnlp_tpu.train import Trainer
    out_dir = cell_mod.out_dir(cell.name + ".precision_control",
                               args.rehearse_cpu)
    ctx = Context(cell=cell, seed=args.seed, seconds=0.0, trace=False,
                  rehearsal=args.rehearse_cpu, t_start=T_START,
                  out_dir=out_dir, log=lambda line: print(line, flush=True))
    traffic = cell.traffic(ctx.rehearsal)
    config = cell.model_config(ctx.rehearsal)
    config.update(
        data_seed=int(ctx.seed), model_path=os.path.join(out_dir, "run"),
        dataset_configs=[{"path": data_mod.ensure_records(
            int(traffic["corpus_bytes"]), int(traffic["file_tokens"]),
            ctx.rehearsal), "type": "text", "weight": 1}])
    params = ModelParameter(config)
    model = Model(params)
    trainer = Trainer(params, model)
    data = make_dataset(params)
    try:
        batch = next(iter(data))
    finally:
        data.close()
    weights_seed, origin = weights_seed_and_origin(cell, args.weights_seed)
    state = trainer.init_state(batch, seed=weights_seed)
    jax.block_until_ready(state.variables)
    ctx.log(seeds_line(weights_seed, origin, state.variables, batch)[0])

    def numbers(checks):
        return {k: checks[k] for k in ("logit_error", "logits_agree")}

    out = {"workload": cell.name, "seed": args.seed,
           "weights_seed": weights_seed,
           "logit_tolerance": float(cell.spec["correct"]["logit_tolerance"]),
           "program": numbers(_reference_check(ctx, config, model, trainer,
                                               None, state, batch))}
    ref = cell_mod.load_reference(cell.config_name)
    tokens = np.asarray(batch["token_x"])[..., 0]
    for name in STREAMS:
        ctx.log(f"the reference with a {name} residual stream:")
        lower = _Logits(jnp.asarray(ref.forward(
            state.variables, tokens, config,
            stream_dtype=getattr(jnp, name))))
        out[name + "_stream"] = numbers(_reference_check(
            ctx, config, lower, lower, None, state, batch))
    out["separates"] = bool(out["program"]["logits_agree"]
                            and not out["float8_e4m3fn_stream"]
                            ["logits_agree"])
    print(json.dumps(out), flush=True)
    if args.rehearse_cpu:
        return EXIT_REHEARSAL
    return 0 if out["separates"] else EXIT_NOT_SEPARATED


if __name__ == "__main__":
    sys.exit(main())
