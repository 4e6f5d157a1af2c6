"""The plain references against ``Model.apply`` at a tiny size on the CPU."""
import importlib
import json
import os

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    #: every configuration of the benchmark, so one a later PR appends is
    #: checked without an edit here
    CONFIGS = [c["name"] for c in json.load(_f)["configs"]]
TINY = {"depth": 2, "heads": 4, "features_per_head": 16,
        "sequence_length": 128, "train_batch_size": 2}


def _program_logits(config):
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    params = ModelParameter(config)
    model = Model(params)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, (2, 128, 1)).astype(np.int32)
    batch = {"token_x": tokens, "token_y": np.roll(tokens, -1, axis=1)}
    variables = model.init(batch, seed=7)
    info = model.apply(variables, batch)
    return (variables, tokens[..., 0], batch["token_y"][..., 0],
            np.asarray(info.token_out.data.astype(np.float32))[:, :, 0, :],
            float(info.total_loss.data))


@pytest.mark.parametrize("name", CONFIGS)
@pytest.mark.parametrize("dtype,tolerance", [
    # float32 program against float32 reference: only summation order
    # differs, so this pins the EQUATIONS (a wrong scale, a missing norm or
    # a shifted mask is off by orders of magnitude more)
    ("float32", 2e-5),
    # the configurations' own bfloat16: every activation, the residual
    # stream and the logits themselves are rounded to 8 bits of mantissa
    # (2^-8 = 0.4% each); measured here 1-3.5% of the reference's largest
    # logit over depths 2-16.  2^-4 is the bound the chip runs use (cells'
    # "logit_tolerance"): an 8-bit float format rounds 16 times coarser
    # and lands at 30% or more, so a lower precision than the
    # configuration states fails it
    ("bfloat16", 2 ** -4),
])
def reference_matches_program_test(name, dtype, tolerance):
    with open(os.path.join(REPO, "benchmark", "configs", f"{name}.json")) as f:
        config = dict(json.load(f)["config"], **TINY,
                      calculation_dtype=dtype, sequence_parallel=1)
    variables, tokens, targets, logits, loss = _program_logits(config)
    ref = importlib.import_module(f"benchmark.reference.{name}")
    from benchmark.reference import common
    want = np.asarray(ref.forward(variables, tokens, config))
    assert want.shape == logits.shape
    err = np.max(np.abs(want - logits)) / np.max(np.abs(want))
    assert err < tolerance, (name, dtype, err)
    want_loss = float(common.loss_of(want, targets, config["z_loss"]))
    # the program reports its loss in the calculation dtype
    ulp = 2.0 ** -18 if dtype == "float32" else 2.0 ** -5
    assert abs(want_loss - loss) <= ulp, (want_loss, loss)
