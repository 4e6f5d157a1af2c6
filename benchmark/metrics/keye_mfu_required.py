"""Required train FLOPs per token of what this expert-parallel rank holds of
Keye-VL-2.0's language model (``roofline/keye_costs.py``: 3 x the forward's
matmuls — the attention's projections, scores and weighted values over the
KEPT pairs only, the indexer's projections and its scores over every visible
pair, the router, the held experts' share of a token's choices, the head over
the slice — plus, once, the index loss's second ``q k^T`` over the kept
pairs, which has no backward; recomputation not credited) times the measured
tokens/s/chip over the chip's bf16 peak, percent: the whole step's share.  It
cannot pass 100: every counted operation is a matmul the step has to run at
least once, and nothing masked or recomputed is counted."""
from ..roofline import costs, keye_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or "index_topk" not in run.config:
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * keye_costs.train_flops_per_token(run.config) * rate / peak
