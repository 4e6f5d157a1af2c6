"""The key-at-a-time selected flash kernels' share of their roofline,
percent, each call costed over the KEPT (query, key) pairs only
(``roofline/keye_costs.py select_cost``: a closed form of the length and
``index_topk``).  The least time the chip could take for all calls (the
larger of required operations over the peak FLOP/s and bytes over the peak
bytes/s) over the time they took.  It cannot pass 100: the kernels run at
least the kept pairs' matmuls (the rest of every tile they visit on top: a
learned choice of single keys leaves few 512 x 512 tiles empty) and move at
least the counted tensors once."""
from ..lib import readers
from ..roofline import costs, keye_costs
from ..trace import reduce as reduce_mod
from .keye_select_flash_time_share import KERNELS

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None or "index_topk" not in run.config:
        return None
    kinds = reduce_mod.kernel_stats(run.trace, KERNELS)
    layers = keye_costs.attention_layers(run.config)
    if not kinds or not layers:
        return None
    peak = costs.peaks(run.result.device["kind"])
    least = took = 0.0
    for kind, (seconds, calls) in sorted(kinds.items()):
        # every layer of the cut holds the same head counts
        flops, bytes_ = keye_costs.select_cost(kind, layers[0], run.config)
        floor, bound = costs.least_seconds(flops, bytes_, peak)
        run.notes.append(
            f"{kind}: {calls} calls, {seconds / calls * 1e3:.4f} ms each, "
            f"{flops / 1e9:.3f} GFLOP and {bytes_ / 1e6:.3f} MB a call, "
            f"{bound}-bound floor {floor * 1e3:.4f} ms "
            f"({100 * floor * calls / seconds:.2f}%)")
        least += floor * calls
        took += seconds
    return readers.share(least, took)
