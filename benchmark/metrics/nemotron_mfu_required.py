"""Required train FLOPs per token of what this rank of the tensor-parallel
pair x expert-parallel group holds (3 x forward from
``roofline/nemotron_costs.py``: Mamba-2's projections and grouped scan,
attention's projections and triangle at this half's heads, the router, the
latent's projections, the shared expert, the routed experts at ``top_k x
held / experts`` — the ACTIVE parameters —, the head over the slice;
recomputation not credited) times the measured tokens/s/chip over the chip's
bf16 peak, percent.  It cannot pass 100: every counted operation is a matmul
the step has to run at least once, and nothing masked or recomputed is
counted."""
from ..roofline import costs, nemotron_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or not run.config.get("moe_latent_width"):
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * nemotron_costs.train_flops_per_token(run.config) * rate \
        / peak
