"""The program's gauge ``hbnlp_ssd_log_decay_min``: the most negative
cumulative ``dt * A`` inside a chunk, over all Mamba-2 layers of the newest
step the program had read when the run ended; ``exp`` of it is the smallest
decay the chunked scan formed (below -87 it is 0 in float32, which is
exact enough: the scan forms decays from differences, never quotients)."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.counter(run, "hbnlp_ssd_log_decay_min")
