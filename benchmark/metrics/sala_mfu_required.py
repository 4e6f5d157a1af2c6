"""Required train FLOPs per token of what this tensor-parallel rank holds of
MiniCPM-SALA (``roofline/sala_costs.py``: 3 x the forward's matmuls — the
held heads' projections, the lightning rule at its chunk's lower triangle,
attention over the KEPT pairs only, the whole MLP, the head over the slice —
with the indexer's scores counted once, since the selection has no backward;
recomputation not credited) times the measured tokens/s/chip over the chip's
bf16 peak, percent: the whole step's share.  It cannot pass 100: every
counted operation is a matmul the step has to run at least once, and nothing
masked or recomputed is counted."""
from ..roofline import costs, sala_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or "lightning_heads" not in run.config:
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * sala_costs.train_flops_per_token(run.config) * rate / peak
