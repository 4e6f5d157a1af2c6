"""CCA's mixing around the kernel against its roofline, percent: the least
time the chip could take for the operations and bytes one step REQUIRES of
the q-k mean, the two convolutions, the normalisation, the rotary embedding
and the value shift (``roofline/zaya_costs.py cca_mix_cost``: the grouped
convolution's matmuls forward and backward; the latents, q, k, v and their
cotangents read and written once; a replayed forward not credited) over the
device time of scopes ``body/cca/qk_mean|conv|qk_norm|rope|value_shift``.
It cannot pass 100: the scopes' instructions read and write at least those
tensors once, the checkpoint's replay on top."""
import re

from ..lib import readers
from ..roofline import costs, zaya_costs
from .zaya_cca_mix_time_share import mix_seconds

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    parts = mix_seconds(run)
    if parts is None:
        return None
    rx = re.compile(run.cell.spec["programs"]["step"])
    steps = sum(len(ds) for name, ds in run.trace["modules"].items()
                if rx.search(name))
    layers = zaya_costs.count(run.config, "cca")
    if not steps or not layers:
        return None
    flops, bytes_ = zaya_costs.cca_mix_cost(run.config)
    floor, bound = costs.least_seconds(
        flops, bytes_, costs.peaks(run.result.device["kind"]))
    took = sum(parts.values())
    run.notes.append(
        f"cca mixing: {steps} steps x {layers} layers, "
        f"{flops / 1e9:.3f} GFLOP and {bytes_ / 1e6:.3f} MB a layer a step, "
        f"{bound}-bound floor {floor * 1e3:.4f} ms; the scopes took "
        f"{took / steps / layers * 1e3:.4f} ms a layer a step")
    return readers.share(floor * steps * layers, took)
