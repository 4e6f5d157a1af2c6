"""Device self time on instructions of scope ``body/mlp`` — the dense gated
MLP after every mixer, forward, recomputed and backward — over busy time,
percent."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.scope_share(run, "body/mlp")
