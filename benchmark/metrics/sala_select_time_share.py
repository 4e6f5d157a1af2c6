"""Device self time on the sparse attention's INDEXER — the steps
``compress`` (pooled keys), ``index`` (softmax scores over them, summed over
a K/V group's heads) and ``select`` (block maxima, forced blocks, the top-k by
rank) of scope ``body/attention/sparse_attention`` — over busy time, percent.
It runs once a step: the choice is saved with ``(out, lse)``, the replay
chooses nothing and the selection has no backward."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"
STEPS = ("compress", "index", "select")


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {step: scopes[f"body/attention/sparse_attention/{step}"]
             for step in STEPS
             if f"body/attention/sparse_attention/{step}" in scopes}
    if not parts:
        return None
    busy = run.trace["busy_s"]
    run.notes.append("the indexer by step: " + ", ".join(
        f"{k} {100 * v / busy:.3f}%" for k, v in parts.items()))
    return readers.share(sum(parts.values()), busy)
