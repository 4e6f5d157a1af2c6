"""Device self time on the learned indexer's scoring and choosing — steps
``index`` (the indexer's projections, its LayerNorm and rotary, the ReLU
scores of every visible pair an index head at a time) and ``select`` (the
exact top-k: the counting passes of the bisection, ties, the choice packed
to bits) of scope ``body/attention/sparse_attention`` — over busy time,
percent.  The notes give each step and the closed forms of what the choice
keeps (``roofline/keye_costs.py``)."""
from ..lib import program_readers, readers
from ..roofline import keye_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"
STEPS = ("index", "select")


def read(run):
    if "index_topk" not in run.config:
        return None
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {step: scopes[f"body/attention/sparse_attention/{step}"]
             for step in STEPS
             if f"body/attention/sparse_attention/{step}" in scopes}
    if not parts:
        return None
    busy = run.trace["busy_s"]
    pairs = keye_costs.kept_pairs(run.config) \
        / keye_costs.visible_pairs(run.config)
    run.notes.append(
        "the indexer by step: " + ", ".join(
            f"{k} {100 * v / busy:.3f}%" for k, v in parts.items())
        + f"; closed forms: kept keys over visible keys, mean over queries "
        f"{100 * keye_costs.kept_key_share(run.config):.4f}%, kept pairs "
        f"over visible pairs {100 * pairs:.4f}%, queries that left a key out "
        f"{keye_costs.choosing_query_share(run.config):.4f}")
    return readers.share(sum(parts.values()), busy)
