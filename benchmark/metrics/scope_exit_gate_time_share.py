"""Device self time on instructions of scope ``exit_gate`` — a looped model's
gate, its distribution over the passes, the entropy and the weighting of the
passes' losses, forward and backward — over busy time, percent."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.scope_share(run, "exit_gate")
