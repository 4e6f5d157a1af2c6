"""The flash-attention kernels' share of their roofline, percent, where EVERY
layer is a latent attention layer with a rotary shared key part (layer 0,
the body's, the multi-token-prediction module's): each call costed at the
layer's TWO widths — scores at the key's 192, weighted values at the value's
128, every tensor's bytes at its own — over the lower triangle
(``roofline/kimi_costs.py flash_cost``, which reads this configuration's
layer strings: the calls are Kimi-Linear's ``[32, 16384, 192 / 128]``).  The
least time the chip could take for all calls (the larger of required
operations over the peak FLOP/s and bytes over the peak bytes/s) over the
time they took: the forward once a layer a step where the stash keeps ``(out,
lse)``, twice where it does not, the backward (fused, or the dq and dk/dv
pair) once.  It cannot pass 100: the kernels run at least the triangle's
matmuls (the dead parts of their diagonal tiles on top) and move at least the
counted tensors once."""
from ..lib import readers
from ..roofline import costs, joyai_costs, kimi_costs
from ..trace import reduce as reduce_mod

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None or not run.config.get("mtp_depth") \
            or not joyai_costs.count(run.config, "latent"):
        return None
    kinds = reduce_mod.kernel_stats(run.trace, r"^flash_")
    if not kinds:
        return None
    peak = costs.peaks(run.result.device["kind"])
    least = took = 0.0
    for kind, (seconds, calls) in sorted(kinds.items()):
        flops, bytes_ = kimi_costs.flash_cost(kind, run.config)
        floor, bound = costs.least_seconds(flops, bytes_, peak)
        run.notes.append(
            f"{kind}: {calls} calls, {seconds / calls * 1e3:.4f} ms each, "
            f"{flops / 1e9:.3f} GFLOP and {bytes_ / 1e6:.3f} MB a call, "
            f"{bound}-bound floor {floor * 1e3:.4f} ms "
            f"({100 * floor * calls / seconds:.2f}%)")
        least += floor * calls
        took += seconds
    return readers.share(least, took)
