"""Seconds ``Trainer.init_state`` spends building the optimizer's slots
(``optimizer.init``): the program's span ``setup/opt_init``."""
from ..lib import program_readers

LAYER = "L0_entry"
MOVES = "setup_s"


def read(run):
    return program_readers.span_seconds(run, "setup/opt_init")
