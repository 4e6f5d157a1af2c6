"""Required train FLOPs per token of the looped model (``roofline/
ouro_costs.py``: 3 x the forward's matmuls — ``loop_steps`` passes of
``depth`` layer applications with causal scores, and a head pass each;
replays not credited) times the measured tokens/s/chip over the chip's bf16
peak, percent: the whole step's share.  It cannot pass 100: every counted
operation is a matmul the step has to run at least once."""
from ..roofline import costs, ouro_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or run.config.get("loop_steps", 1) < 2:
        return None
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * ouro_costs.train_flops_per_token(run.config) * rate / peak
