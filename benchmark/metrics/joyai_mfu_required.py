"""Required train FLOPs per token of what this rank of the expert-parallel
group holds (3 x forward from ``roofline/joyai_costs.py``: every latent
attention layer's projections through both latents and its triangle at key
192 / value 128 — layer 0's, the body's and the module's —, the dense MLP,
the router over 256, the shared expert, the routed experts at ``top_k x held
/ experts`` — the ACTIVE parameters —, the head over the slice TWICE, the
module's join; recomputation not credited) times the measured tokens/s/chip
over the chip's bf16 peak, percent: the share of the whole step.  It cannot
pass 100: every counted operation is a matmul the step has to run at least
once, and nothing masked or recomputed is counted."""
from ..roofline import costs, joyai_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or not run.config.get("mtp_depth"):
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    required = joyai_costs.train_flops_per_token(run.config)
    run.notes.append(
        f"required forward FLOPs a token {required / 3:.0f}: "
        f"{joyai_costs.count(run.config, 'latent')} latent attention layers, "
        f"{joyai_costs.count(run.config, 'sparse')} sparse, "
        f"{joyai_costs.count(run.config, 'dense')} dense; the head "
        f"{joyai_costs.head_flops_per_token(run.config):.0f}, the module's "
        f"join {joyai_costs.join_flops_per_token(run.config):.0f}")
    return 100.0 * required * rate / peak
