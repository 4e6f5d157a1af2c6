"""Required train FLOPs per TRAINED token of what this expert-parallel rank
holds of SDAR-30B-A3B under block-diffusion training
(``roofline/sdar_costs.py``: 3 x the forward's matmuls — every layer's
projections and sparse layer over BOTH halves of the doubled stream, the
attention's scores and weighted values over the live pairs of the
block-diffusion mask, the head over the noised half alone; recomputation not
credited) times the measured trained tokens/s/chip over the chip's bf16 peak,
percent: the whole step's share.  It cannot pass 100: every counted operation
is a matmul the step has to run at least once, and nothing masked or
recomputed is counted."""
from ..roofline import costs, sdar_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or not run.config.get("diffusion_block"):
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    config = run.config
    layer = sdar_costs.attention_layers(config)[0]
    parts = sdar_costs.layer_flops_per_token(layer, config)
    run.notes.append(
        "required forward FLOPs a trained token: "
        f"{sdar_costs.forward_flops_per_token(config):.0f} = the head "
        f"{sdar_costs.head_flops_per_token(config):.0f} + "
        f"{config['depth']} layers of projections "
        f"{parts['projections']:.0f}, live pairs {parts['attention']:.0f}, "
        f"router and held experts "
        f"{sdar_costs.sparse_flops_per_token(config):.0f}")
    return 100.0 * sdar_costs.train_flops_per_token(config) * rate / peak
