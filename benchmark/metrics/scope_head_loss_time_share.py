"""Device self time on instructions of scope ``head_loss`` — the head matmul
with its cross-entropy, forward and both gradients — over busy time,
percent."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.scope_share(run, "head_loss")
