"""Required train FLOPs per token of the gated delta-rule / attention hybrid
(3 x forward from ``roofline/olmo_hybrid_costs.py``: the chunked rule and the
attention scores at their lower triangles, the triangular system by
substitution, recomputation not credited) times the measured tokens/s/chip
over the chip's bf16 peak, percent."""
from ..roofline import costs, olmo_hybrid_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or "delta_heads" not in run.config:
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * olmo_hybrid_costs.train_flops_per_token(run.config) \
        * rate / peak
