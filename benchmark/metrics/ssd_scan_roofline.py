"""The chunked scan against its roofline, percent: the least time the chip
could take for the operations and bytes one step REQUIRES of it
(``roofline/granite_costs.scan_cost``: forward and backward of every Mamba-2
layer; what a memory strategy recomputes and what a fused kernel would keep
on the chip are not credited) over the device time of scope
``body/mamba/ssd``."""
import re

from ..lib import program_readers, readers
from ..roofline import costs, granite_costs

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None or "body/mamba/ssd" not in scopes:
        return None
    rx = re.compile(run.cell.spec["programs"]["step"])
    steps = sum(len(ds) for name, ds in run.trace["modules"].items()
                if rx.search(name))
    if not steps:
        return None
    flops, bytes_ = granite_costs.scan_cost(run.config)
    peak = costs.peaks(run.result.device["kind"])
    floor, bound = costs.least_seconds(flops, bytes_, peak)
    took = scopes["body/mamba/ssd"]
    layers = granite_costs.mamba_layers(run.config)
    run.notes.append(
        f"chunked scan: {steps} steps x {layers} layers, "
        f"{flops / 1e12:.4f} TFLOP and {bytes_ / 1e9:.4f} GB a layer a "
        f"step, {bound}-bound floor {floor * 1e3:.4f} ms; scope "
        f"body/mamba/ssd took {took / steps / layers * 1e3:.4f} ms a layer "
        f"a step")
    return readers.share(floor * steps * layers, took)
