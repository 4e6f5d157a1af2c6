"""The program's gauge ``hbnlp_moe_top1_weight_mean``: the chosen expert's
probability, the mean over the tokens of the newest step the program had
read when the run ended, in the top-1 layer where it is smallest.  ``1 /
experts`` (0.0625 at 16) is a router that says nothing; the expert's output
is scaled by it.  The notes give CCA's logit bound beside it
(``hbnlp_cca_logit_scale_max``) and the carried router states' bytes."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = program_readers.counter(run, "hbnlp_moe_top1_weight_mean")
    if value is None:
        return None
    bound = program_readers.counter(run, "hbnlp_cca_logit_scale_max")
    carry = program_readers.counter(run, "hbnlp_router_carry_bytes")
    run.notes.append(
        f"top-1 weight {value:.6f} in the layer where it is smallest; cca "
        f"logit bound sqrt(d) |tau| {bound}; router states carried between "
        f"blocks {carry} bytes")
    return value
