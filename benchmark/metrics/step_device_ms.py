"""Median device time of one run of the train step program."""
from ..lib import readers

LAYER = "L2_step_programs"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return readers.module_median_ms(run, "step")
