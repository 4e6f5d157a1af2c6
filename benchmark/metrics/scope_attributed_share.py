"""Share of device busy time (self time) on instructions whose ``tf_op`` folds
into a model scope (``analysis/cost_ledger.scope_key``), percent.  The
notes list every scope's share, the largest unscoped instructions and each
collective's scope."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.scope_attributed_share(run)
