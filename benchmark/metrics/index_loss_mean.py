"""The program's gauge ``hbnlp_index_loss``: the learned indexer's KL loss to
the attention's head-mean probabilities over the kept keys, nat, the mean
over the indexed layers of the newest step the program had read when the run
ended (0 = the indexer ranks the kept keys as the attention weighs them).
The notes give the largest kept |index score| (``hbnlp_index_score_abs_max``)."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = program_readers.counter(run, "hbnlp_index_loss")
    if value is None:
        return None
    run.notes.append(
        "largest kept |index score| "
        f"{program_readers.counter(run, 'hbnlp_index_score_abs_max')}")
    return value
