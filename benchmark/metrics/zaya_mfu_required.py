"""Required train FLOPs per token of what this expert-parallel rank holds (3
x forward from ``roofline/zaya_costs.py``: CCA's projections, its grouped
convolution and its scores over the lower triangle, the router's MLP, the ONE
expert a token chose at the share of the pairs that really landed on the held
experts — the program's own count, ``hbnlp_moe_held_pairs_total`` over
``hbnlp_moe_routed_pairs_total``; a balanced router's ``held / experts``
where the program counted none — and the head over the slice; recomputation
not credited) times the measured tokens/s/chip over the chip's bf16 peak,
percent.  It cannot pass 100: every counted operation is a matmul the step
has to run at least once, and nothing masked or recomputed is counted."""
from ..lib import program_readers
from ..roofline import costs, zaya_costs

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    rate = run.result.end_to_end.get("train_tokens_per_sec_chip")
    if rate is None or "moe_router_width" not in run.config:
        return None
    held = program_readers.counter(run, "hbnlp_moe_held_pairs_total")
    routed = program_readers.counter(run, "hbnlp_moe_routed_pairs_total")
    share = held / routed if held is not None and routed else None
    run.notes.append(
        "pairs on the held experts: "
        + (f"{100 * share:.4f}% (the program's count over the run)"
           if share is not None else
           f"{100 * zaya_costs.held_share(run.config):.4f}% (a balanced "
           f"router's: the program counted none)"))
    peak = costs.peaks(run.result.device["kind"])["bf16_flops_per_s"]
    return 100.0 * zaya_costs.train_flops_per_token(run.config, share) \
        * rate / peak
