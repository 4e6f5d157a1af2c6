"""The program's gauge ``hbnlp_mtp_loss_over_main``: the multi-token-prediction
module's cross-entropy (the token two on) over the main model's next-token
cross-entropy, of the newest step the program had read when the run ended:
near 1 at initialisation, above it once the main model has learnt more of the
next token than the module of the one after.  The notes give the module's
loss itself (``hbnlp_mtp_loss``), which the step's reported loss does not
hold."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = program_readers.counter(run, "hbnlp_mtp_loss_over_main")
    if value is None:
        return None
    run.notes.append(
        f"hbnlp_mtp_loss {program_readers.counter(run, 'hbnlp_mtp_loss')}")
    return value
