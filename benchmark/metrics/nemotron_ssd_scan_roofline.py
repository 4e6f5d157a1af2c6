"""The grouped chunked-scan KERNEL PAIR against its roofline, percent: the
least time the chip could take for the operations and bytes one step
REQUIRES of the scan (``roofline/nemotron_costs.scan_cost``: forward and
backward of every Mamba-2 layer, ``B`` / ``C`` bytes once a group; the
forward the ``checkpoint`` replay runs a second time is not credited) over
the device time of the kernels the trace names ``ssd_scan_*``.  Nothing to
read where the scan is XLA's einsums (a program without the kernels, a CPU
rehearsal)."""
import re

from ..lib import readers
from ..roofline import costs, nemotron_costs
from ..trace import reduce as reduce_mod

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None or not run.config.get("mamba_groups"):
        return None
    kinds = reduce_mod.kernel_stats(run.trace, r"^ssd_scan")
    rx = re.compile(run.cell.spec["programs"]["step"])
    steps = sum(len(ds) for name, ds in run.trace["modules"].items()
                if rx.search(name))
    layers = nemotron_costs.count(run.config, "mamba")
    if not kinds or not steps or not layers:
        return None
    flops, bytes_ = nemotron_costs.scan_cost(run.config)
    floor, bound = costs.least_seconds(
        flops, bytes_, costs.peaks(run.result.device["kind"]))
    took = sum(seconds for seconds, _ in kinds.values())
    run.notes.append(
        f"grouped scan kernels: {steps} steps x {layers} layers, "
        f"{flops / 1e12:.4f} TFLOP and {bytes_ / 1e9:.4f} GB a layer a "
        f"step, {bound}-bound floor {floor * 1e3:.4f} ms; "
        + ", ".join(f"{kind} {calls} calls {seconds / calls * 1e3:.4f} ms "
                    f"each" for kind, (seconds, calls)
                    in sorted(kinds.items()))
        + f": {took / steps / layers * 1e3:.4f} ms a layer a step")
    return readers.share(floor * steps * layers, took)
