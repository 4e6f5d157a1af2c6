"""Device self time on instructions of the step's BACKWARD pass over busy time,
percent: the instructions whose ``tf_op`` the program's ``analysis/
cost_ledger.pass_key`` folds to ``backward`` (``lib/pass_readers.py``).
``None`` where the direction-named kernels refuse the fold;
``pass_replay_time_share``'s notes hold the whole split."""
from ..lib import pass_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return pass_readers.pass_share(run, "backward")
