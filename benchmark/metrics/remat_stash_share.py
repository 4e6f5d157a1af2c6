"""What the memory rule keeps for the backward so that it is not replayed,
over the chip's limit, percent: the sum over ``kind`` of gauge
``hbnlp_remat_stash_bytes{kind}`` (``train/__init__.py publish_stash_plan``)
over ``hbnlp_hbm_bytes{point="step_loaded", kind="limit"}``.  The price paid
for the ``pass_replay_time_share`` beside it.  The bytes are the RULE's own
sum from shapes (``model/remat.py stash_plan``), not a measurement: PR 52 and
PR 61 measured what such bytes cost in ``hbm_step_footprint_share``, a
fraction of themselves, since the scheduler places them in room the step
holds anyway.  The note gives bytes and ``hbnlp_remat_stash_layers`` by
kind."""
from ..lib import pass_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return pass_readers.remat_stash_share(run)
