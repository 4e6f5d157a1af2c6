"""The routed experts' grouped matmuls against their roofline, percent: the
least time the chip could take for the operations and bytes one step
REQUIRES of them (forward and backward; what a memory strategy recomputes is
not credited) over the device time of scope ``body/moe/experts``."""
import re

from ..lib import program_readers, readers
from ..roofline import costs

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def expert_gemm_cost(config: dict, width: int = 2):
    """``(flops, bytes)`` one layer's expert matmuls need in one train step
    on one chip.  Rows: every (token, choice) pair, ``m = tokens * k``.
    Three matmuls (gate, up: ``d -> i``; down: ``i -> d``), each run forward
    and twice more for its two gradients: ``3 x 3 x 2 m d i`` operations.
    Bytes, ``width`` an element: the forward reads rows and all experts'
    weights and writes rows; the row gradient reads the output's gradient and
    the weights; the weight gradient reads both row tensors and writes the
    weights' shape."""
    d = config["heads"] * config["features_per_head"]
    i = int(d * config["intermediate_feed_forward_multiplier"])
    m = config["train_batch_size"] * config["sequence_length"] \
        * config["moe_top_k"]
    weights = config["experts"] * d * i
    flops = 3 * 3 * 2 * m * d * i
    one = (m * d + weights + m * i) * width          # each of the 3 passes
    return flops, 3 * 3 * one


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None or "body/moe/experts" not in scopes:
        return None
    rx = re.compile(run.cell.spec["programs"]["step"])
    steps = sum(len(ds) for name, ds in run.trace["modules"].items()
                if rx.search(name))
    if not steps:
        return None
    flops, bytes_ = expert_gemm_cost(run.config)
    peak = costs.peaks(run.result.device["kind"])
    floor, bound = costs.least_seconds(flops, bytes_, peak)
    took = scopes["body/moe/experts"]
    layers = run.config["depth"]
    run.notes.append(
        f"expert matmuls: {steps} steps x {layers} layers, "
        f"{flops / 1e12:.4f} TFLOP and {bytes_ / 1e9:.4f} GB a layer a "
        f"step, {bound}-bound floor {floor * 1e3:.4f} ms; scope "
        f"body/moe/experts took {took / steps / layers * 1e3:.4f} ms a "
        f"layer a step")
    return readers.share(floor * steps * layers, took)
