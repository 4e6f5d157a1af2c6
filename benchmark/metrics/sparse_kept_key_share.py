"""The program's gauge ``hbnlp_sparse_kept_key_share``: the keys a query of a
sparse attention layer kept over the keys it may see, the mean over the
queries of the newest step the program had read when the run ended, percent.
The notes give the closed form it should read (``roofline/sala_costs.py``:
how many blocks a query keeps does not depend on the weights) and the share
of the queries that left a block out (``hbnlp_sparse_choosing_query_share``)."""
from ..lib import program_readers
from ..roofline import sala_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = program_readers.counter(run, "hbnlp_sparse_kept_key_share")
    if value is None:
        return None
    chose = program_readers.counter(run, "hbnlp_sparse_choosing_query_share")
    run.notes.append(
        f"kept keys over visible keys {100 * value:.4f}% (closed form "
        f"{100 * sala_costs.kept_key_share(run.config):.4f}%); queries that "
        f"left a block out {chose} (closed form "
        f"{sala_costs.choosing_query_share(run.config):.4f})")
    return 100.0 * value
