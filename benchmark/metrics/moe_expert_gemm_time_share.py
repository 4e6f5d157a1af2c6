"""Device self time on instructions of scope ``body/moe/experts`` — the
three grouped matmuls of the routed experts, forward, recomputed and
backward, and the gate between them — over busy time, percent."""
from ..lib import program_readers

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.scope_share(run, "body/moe/experts")
