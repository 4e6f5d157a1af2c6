"""Device self time on the indexer's loss pass — step ``index_loss`` of scope
``body/attention/sparse_attention``: every attention head's probabilities
over the kept keys again (the loss's second ``q k^T``), the scores again,
the KL value and the hand-made gradients to the index queries, key and
weights, a chunk of 512 queries at a time — over busy time, percent."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if "index_topk" not in run.config:
        return None
    scopes = program_readers.scope_seconds(run)
    key = "body/attention/sparse_attention/index_loss"
    if scopes is None or key not in scopes:
        return None
    return 100.0 * scopes[key] / run.trace["busy_s"]
