"""Device time of the WINDOWED flash kernels (``flash_*_window``: the band of
4,096 keys, forward and backward) over the device's busy time, percent.  The
notes give each kind beside the causal kernels' (the global layers')."""
from ..lib import readers
from ..trace import reduce as reduce_mod

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None:
        return None
    kinds = reduce_mod.kernel_stats(run.trace, r"^flash_")
    windowed = {k: v for k, v in kinds.items() if k.endswith("_window")}
    if not windowed:
        return None           # a program without the windowed kernels
    busy = run.trace["busy_s"]
    run.notes.append("flash kernels by kind: " + ", ".join(
        f"{k} {calls} calls {100 * s / busy:.2f}%"
        for k, (s, calls) in sorted(kinds.items())))
    return readers.share(sum(s for s, _ in windowed.values()), busy)
