"""One minus the union of the device's operation intervals over the traced
window, percent."""
from ..lib import readers

LAYER = "L5_device"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None:
        return None
    return readers.share(run.trace["idle_s"], run.trace["window_s"])
