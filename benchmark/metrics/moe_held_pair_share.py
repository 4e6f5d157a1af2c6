"""The program's gauge ``hbnlp_moe_held_pair_share``: (token, choice) pairs
routed to the experts this rank holds over the pairs routed, all sparse
layers of the newest step the program had read when the run ended, percent
(``experts_held / experts`` = 3.125 when the router is balanced; the
largest layer's share in the notes)."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    share = program_readers.counter(run, "hbnlp_moe_held_pair_share")
    if share is None:
        return None
    worst = program_readers.counter(run, "hbnlp_moe_held_pair_share_max")
    bound = program_readers.counter(run, "hbnlp_moe_held_rows_bound")
    run.notes.append(
        f"held pairs: {100 * share:.4f}% of the pairs routed over all sparse "
        f"layers, {100 * (worst or 0):.4f}% in the fullest; the static row "
        f"buffer holds {bound} rows a layer")
    return 100.0 * share
