"""The causal flash-attention kernels' device time (``flash_*_causal``: CCA's
scores and weighted values at 8 query heads in the latent) over the device's
busy time, percent.  The notes give each kind."""
from ..lib import readers
from ..trace import reduce as reduce_mod

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None or "moe_router_width" not in run.config:
        return None
    kinds = reduce_mod.kernel_stats(run.trace, r"^flash_")
    if not kinds:
        return None
    busy = run.trace["busy_s"]
    run.notes.append("flash kernels by kind: " + ", ".join(
        f"{k} {calls} calls {100 * s / busy:.2f}%"
        for k, (s, calls) in sorted(kinds.items())))
    return readers.share(sum(s for s, _ in kinds.values()), busy)
