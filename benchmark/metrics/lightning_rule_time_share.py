"""Device self time on instructions of scope ``body/lightning/rule`` — the
chunked decayed linear attention: the scores inside a chunk under the
constant decay, the chunks' states, the serial recurrence over the chunks and
the entering states' part, forward, recomputed and backward — over busy
time, percent.  The notes split it by the rule's own steps."""
from ..lib import program_readers, readers

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"
SCOPE = "body/lightning/rule"
STEPS = ("intra_chunk", "chunk_states", "inter_chunk", "state_out")


def read(run):
    share = program_readers.scope_share(run, SCOPE)
    if share is None:
        return None
    tf_op = program_readers._tf_ops(run.result.trace_path) or {}
    scopes = program_readers._op_scopes(run.result.trace_path) or {}
    by_step = {}
    for name, seconds in run.trace["ops"].items():
        if scopes.get(name) != SCOPE:
            continue
        step = next((s for s in STEPS if f"/{s}/" in f"/{tf_op[name]}/"),
                    "other")
        by_step[step] = by_step.get(step, 0.0) + seconds
    run.notes.append(f"{SCOPE} by step: " + ", ".join(
        f"{k} {readers.share(v, run.trace['busy_s']):.2f}%"
        for k, v in sorted(by_step.items(), key=lambda kv: -kv[1])))
    return share
