"""The program's gauge ``hbnlp_moe_load_max_over_mean``: (token, choice)
pairs of the busiest expert over the mean, in the worst routed layer of the
newest step the program had read when the run ended; 1.0 = balanced."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.counter(run, "hbnlp_moe_load_max_over_mean")
