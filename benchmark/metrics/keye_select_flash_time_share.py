"""The key-at-a-time selected flash kernels' device time (``flash_*_select``
in a configuration with a learned indexer: forward, dq and dk/dv over the
keys each query kept) over the device's busy time, percent.  The notes give
each kind."""
from ..lib import readers
from ..trace import reduce as reduce_mod

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"
KERNELS = r"^flash_.*_select"


def read(run):
    if run.trace is None or "index_topk" not in run.config:
        return None
    kinds = reduce_mod.kernel_stats(run.trace, KERNELS)
    if not kinds:
        return None
    busy = run.trace["busy_s"]
    run.notes.append("key-selected flash kernels by kind: " + ", ".join(
        f"{k} {calls} calls {100 * s / busy:.2f}%"
        for k, (s, calls) in sorted(kinds.items())))
    return readers.share(sum(s for s, _ in kinds.values()), busy)
