"""The chunked Kimi Delta Attention rule against its roofline, percent: the
least time the chip could take for the operations and bytes one step REQUIRES
of it at chunk 64 (``roofline/kimi_costs.rule_cost``: forward and backward of
every KDA layer, ``g`` a float32 a channel; what a memory strategy recomputes
and what a fused kernel would keep on the chip are not credited) over the
device time of scope ``body/kda/rule`` — XLA's grouped form today, the solve's
Pallas pair inside it; the same work whatever implements it."""
import re

from ..lib import program_readers, readers
from ..roofline import costs, kimi_costs

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"
SCOPE = "body/kda/rule"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None or SCOPE not in scopes:
        return None
    rx = re.compile(run.cell.spec["programs"]["step"])
    steps = sum(len(ds) for name, ds in run.trace["modules"].items()
                if rx.search(name))
    layers = kimi_costs.count(run.config, "kda")
    if not steps or not layers:
        return None
    flops, bytes_ = kimi_costs.rule_cost(run.config)
    floor, bound = costs.least_seconds(
        flops, bytes_, costs.peaks(run.result.device["kind"]))
    took = scopes[SCOPE]
    run.notes.append(
        f"kda rule: {steps} steps x {layers} layers, "
        f"{flops / 1e12:.4f} TFLOP and {bytes_ / 1e9:.4f} GB a layer a "
        f"step, {bound}-bound floor {floor * 1e3:.4f} ms; scope "
        f"{SCOPE} took {took / steps / layers * 1e3:.4f} ms a layer a step")
    return readers.share(floor * steps * layers, took)
