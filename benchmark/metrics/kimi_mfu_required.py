"""Required train FLOPs per token of what this rank of the expert-parallel
group holds (3 x forward from ``roofline/kimi_costs.py``: KDA's projections,
low-rank pairs and chunked rule, the latent attention's projections and its
triangle at key 192 / value 128, the dense MLP, the router, the shared
expert, the routed experts at ``top_k x held / experts`` — the ACTIVE
parameters —, the head over the slice; recomputation not credited) times the
measured tokens/s/chip over the chip's bf16 peak, percent: the share of the
whole step.  It cannot pass 100: every counted operation is a matmul (or a
decayed product that costs a matmul's pairs) the step has to run at least
once, and nothing masked or recomputed is counted."""
from ..roofline import costs, kimi_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or not kimi_costs.count(run.config, "kda"):
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * kimi_costs.train_flops_per_token(run.config) * rate / peak
