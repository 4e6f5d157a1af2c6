"""The program's gauge ``hbnlp_moe_gate_live_share``: the gate values that
ReLU leaves above zero over the gate values of the pairs routed to the
experts held here, all relu-gated sparse layers of the newest step the
program had read when the run ended, percent (near 50 at initialisation; the
zeros are what the model's deployment skips).  The notes carry the carried
side values' bytes (``hbnlp_router_carry_bytes``)."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = program_readers.counter(run, "hbnlp_moe_gate_live_share")
    if value is None:
        return None
    run.notes.append(
        "hbnlp_router_carry_bytes "
        f"{program_readers.counter(run, 'hbnlp_router_carry_bytes')}")
    return 100.0 * value
