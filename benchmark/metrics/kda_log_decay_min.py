"""The program's gauge ``hbnlp_kda_log_decay_min``: the most negative
cumulative log-decay of any channel inside a chunk, over all KDA layers of
the newest step the program had read when the run ended; ``exp`` of it is the
smallest decay the chunked rule formed (below -87 it is 0 in float32, which
is exact enough: the rule forms decays from differences that are never
positive, never a quotient)."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.counter(run, "hbnlp_kda_log_decay_min")
