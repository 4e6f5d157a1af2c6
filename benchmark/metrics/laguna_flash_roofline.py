"""The flash-attention kernels' share of their roofline, percent, each call
costed by its own kind (``roofline/laguna_costs.py flash_cost``): a
``flash_*_window`` call at the window layers' head count over the BAND's
pairs, a ``flash_*_causal`` call at the global layers' over the triangle's.
The least time the chip could take for all calls (the larger of required
operations over the peak FLOP/s and bytes over the peak bytes/s) over the
time they took.  It cannot pass 100: the kernels run at least the required
pairs' matmuls (the masked halves of their edge tiles on top) and move at
least the counted tensors once."""
from ..lib import readers
from ..roofline import costs, laguna_costs
from ..trace import reduce as reduce_mod

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None:
        return None
    kinds = reduce_mod.kernel_stats(run.trace, r"^flash_")
    if not any(k.endswith("_window") for k in kinds):
        return None           # a program without the windowed kernels
    peak = costs.peaks(run.result.device["kind"])
    least = took = 0.0
    for kind, (seconds, calls) in sorted(kinds.items()):
        flops, bytes_ = laguna_costs.flash_cost(kind, run.config)
        floor, bound = costs.least_seconds(flops, bytes_, peak)
        run.notes.append(
            f"{kind}: {calls} calls, {seconds / calls * 1e3:.4f} ms each, "
            f"{flops / 1e9:.3f} GFLOP and {bytes_ / 1e6:.3f} MB a call, "
            f"{bound}-bound floor {floor * 1e3:.4f} ms "
            f"({100 * floor * calls / seconds:.2f}%)")
        least += floor * calls
        took += seconds
    return readers.share(least, took)
