"""Device self time on REPLAYED forwards over busy time, percent: what the
memory strategy runs again inside the backward (``jax.checkpoint``'s
``rematted_computation``; the revnet / momentum strategies' blocks under the
program's scope ``replay``), by ``analysis/cost_ledger.pass_key``
(``lib/pass_readers.py``).  What ``remat_stash_share`` pays for making
smaller.  The notes hold every pass's share, the replay by scope, the
direction-named kernels' calls by pass beside ``hbnlp_remat_stash_layers``,
the unmarked rest, the share of busy time in fusions (a fusion carries its
root's pass) and what the fold took."""
from ..lib import pass_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return pass_readers.replay_share(run)
