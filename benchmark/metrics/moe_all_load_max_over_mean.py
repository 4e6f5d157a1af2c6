"""The program's gauge ``hbnlp_moe_all_load_max_over_mean``: the pairs of
the busiest of ALL the routed experts over their mean, in the worst
``sigmoid_bias`` sparse layer of the newest step the program had read when
the run ended (1 = balanced) — the load the selection bias's rule answers,
beside ``moe_load_max_over_mean`` over the held experts alone.  The note
gives ``hbnlp_moe_bias_abs_max``, the largest |selection bias| at that step:
the rule moves every entry by ``moe_bias_rate`` a step, so it reads at most
``moe_bias_rate x steps`` and 0 where the rule is not running — a sign of
life with no better direction, hence no metric of its own."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = program_readers.counter(run, "hbnlp_moe_all_load_max_over_mean")
    bias = program_readers.counter(run, "hbnlp_moe_bias_abs_max")
    if value is not None and bias is not None:
        run.notes.append(f"selection bias: largest |b| {bias:.6g}")
    return value
