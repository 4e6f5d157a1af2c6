"""Collective time during which no other operation runs on that chip, over the
device's busy time, percent (mean over the chips)."""
from ..lib import readers

LAYER = "L5_device"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None:
        return None
    return readers.share(run.trace["collective_exposed_s"],
                         run.trace["busy_s"])
