"""Median length of the program's span ``train/step_dispatch``
(``Trainer.step``: key build, placement check, the jitted call's enqueue)
inside the traced window, milliseconds."""
from ..lib import program_readers

LAYER = "L1_host_loop"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.span_median_ms(run, "train/step_dispatch")
