"""The chunked gated delta rule against its roofline, percent: the least time
the chip could take for the operations and bytes one step REQUIRES of it
(``roofline/olmo_hybrid_costs.rule_cost``: forward and backward of every
gated delta-rule layer; what a memory strategy recomputes and what a fused
kernel would keep on the chip are not credited) over the device time of scope
``body/gated_delta/delta_rule``."""
import re

from ..lib import program_readers, readers
from ..roofline import costs, olmo_hybrid_costs

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"
SCOPE = "body/gated_delta/delta_rule"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None or SCOPE not in scopes:
        return None
    rx = re.compile(run.cell.spec["programs"]["step"])
    steps = sum(len(ds) for name, ds in run.trace["modules"].items()
                if rx.search(name))
    if not steps:
        return None
    flops, bytes_ = olmo_hybrid_costs.rule_cost(run.config)
    peak = costs.peaks(run.result.device["kind"])
    floor, bound = costs.least_seconds(flops, bytes_, peak)
    took = scopes[SCOPE]
    layers = olmo_hybrid_costs.delta_layers(run.config)
    run.notes.append(
        f"delta rule: {steps} steps x {layers} layers, "
        f"{flops / 1e12:.4f} TFLOP and {bytes_ / 1e9:.4f} GB a layer a "
        f"step, {bound}-bound floor {floor * 1e3:.4f} ms; scope "
        f"{SCOPE} took {took / steps / layers * 1e3:.4f} ms a layer a step")
    return readers.share(floor * steps * layers, took)
