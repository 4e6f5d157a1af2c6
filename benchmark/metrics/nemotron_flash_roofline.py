"""The flash-attention kernels' share of their roofline, percent, each call
costed at the attention LAYER's own heads — ``q_heads`` over ``kv_heads`` of
its layer string, not the stream's ``heads`` the generic reader assumes —
over the lower triangle (``roofline/nemotron_costs.py flash_cost``).  The
least time the chip could take for all calls (the larger of required
operations over the peak FLOP/s and bytes over the peak bytes/s) over the
time they took.  It cannot pass 100: the kernels run at least the triangle's
matmuls (the masked halves of their diagonal tiles on top) and move at least
the counted tensors once."""
from ..lib import readers
from ..roofline import costs, nemotron_costs
from ..trace import reduce as reduce_mod

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None or not run.config.get("moe_latent_width"):
        return None
    kinds = reduce_mod.kernel_stats(run.trace, r"^flash_")
    if not kinds:
        return None
    peak = costs.peaks(run.result.device["kind"])
    least = took = 0.0
    for kind, (seconds, calls) in sorted(kinds.items()):
        flops, bytes_ = nemotron_costs.flash_cost(kind, run.config)
        floor, bound = costs.least_seconds(flops, bytes_, peak)
        run.notes.append(
            f"{kind}: {calls} calls, {seconds / calls * 1e3:.4f} ms each, "
            f"{flops / 1e9:.3f} GFLOP and {bytes_ / 1e6:.3f} MB a call, "
            f"{bound}-bound floor {floor * 1e3:.4f} ms "
            f"({100 * floor * calls / seconds:.2f}%)")
        least += floor * calls
        took += seconds
    return readers.share(least, took)
