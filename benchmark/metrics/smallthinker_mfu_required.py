"""Required train FLOPs per token of what this expert-parallel rank holds of
SmallThinker-21BA3B (``roofline/smallthinker_costs.py``: 3 x the forward's
matmuls — every layer's projections, the attention's scores and weighted
values over the LIVE pairs (the band of 4,096 in the window layers, the
triangle in the global ones), the early router, the routed experts at ``top_k
x held / experts``, the head over the slice; recomputation not credited) times
the measured tokens/s/chip over the chip's bf16 peak, percent: the whole
step's share.  It cannot pass 100: every counted operation is a matmul the
step has to run at least once, and nothing masked or recomputed is counted.
The notes give the forward by part."""
from ..roofline import costs, smallthinker_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or not smallthinker_costs.early_routers(run.config):
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    parts = smallthinker_costs.forward_parts_per_token(run.config)
    run.notes.append(
        f"required forward FLOPs a token: {sum(parts.values()):.0f} = "
        + ", ".join(f"{name} {value:.0f}" for name, value in parts.items()))
    return 100.0 * smallthinker_costs.train_flops_per_token(run.config) \
        * rate / peak
