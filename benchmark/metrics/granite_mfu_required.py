"""Required train FLOPs per token of the hybrid Mamba-2 / attention model (3
x forward from ``roofline/granite_costs.py``: the chunked scan and the
attention scores at their lower triangles, recomputation not credited) times
the measured tokens/s/chip over the chip's bf16 peak, percent."""
from ..roofline import costs, granite_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or "mamba_heads" not in run.config:
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * granite_costs.train_flops_per_token(run.config) * rate \
        / peak
