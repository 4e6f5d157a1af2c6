"""Device self time on instructions inside a looped model's passes — the
regions ``loop/pass<t>`` of the device trace: a pass's blocks, forward,
replay and backward, and its final norm — over busy time, percent.  The notes
give each pass's milliseconds a step: the passes do equal work, so a
difference between them is the memory strategy's (what a pass saves and what
it replays)."""
import re

from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"

_PASS = re.compile(r"(?:^|[/(])loop/pass(\d+)(?:[/)]|$)")


def read(run):
    if run.trace is None or not run.result.trace_path:
        return None
    ops = program_readers._tf_ops(run.result.trace_path)
    if ops is None:
        return None
    passes = {}
    for name, seconds in run.trace["ops"].items():
        hit = _PASS.search(ops.get(name, ""))
        if hit:
            index = int(hit.group(1))
            passes[index] = passes.get(index, 0.0) + seconds
    if not passes:
        run.notes.append("no instruction of a region loop/pass<t> in the "
                         "trace")
        return None
    steps = max(1, int(run.cell.traffic().get("trace_steps", 1)))
    run.notes.append("loop passes, ms a step: " + ", ".join(
        f"pass{index} {seconds / steps * 1e3:.3f}"
        for index, seconds in sorted(passes.items())))
    return readers.share(sum(passes.values()), run.trace["busy_s"])
