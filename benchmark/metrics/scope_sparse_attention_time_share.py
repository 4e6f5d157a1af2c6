"""Device self time on instructions of block-selected sparse attention —
scope ``body/attention/sparse_attention`` and its steps ``compress``,
``index``, ``select`` (the indexer) and ``attend`` (the selected flash
kernels) — over busy time, percent.  The layer's projections, norms and gate
stay in ``body/attention``.  The notes give each step."""
from ..lib import readers
from .scope_lightning_time_share import parts_of

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    parts = parts_of(run, "body/attention/sparse_attention")
    if parts is None:
        return None
    return readers.share(sum(parts.values()), run.trace["busy_s"])
