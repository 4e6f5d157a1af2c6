"""Required train FLOPs per token of what this expert-parallel rank holds (3
x forward from ``roofline/laguna_costs.py``: projections at each layer
type's head count, the band for window layers and the triangle for global
ones, the dense MLP, router, shared expert, routed experts at ``top_k x held
/ experts``, the head over the slice; recomputation not credited) times the
measured tokens/s/chip over the chip's bf16 peak, percent.  It cannot pass
100: every counted operation is a matmul the step has to run at least once,
and nothing masked or recomputed is counted."""
from ..roofline import costs, laguna_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or "experts_held" not in run.config:
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * laguna_costs.train_flops_per_token(run.config) * rate / peak
