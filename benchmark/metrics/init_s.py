"""Seconds from the end of imports and input set-up to a usable model:
``Trainer.init_state`` for training, spawn until ``/health`` answers for
serving."""
from ..lib import readers

LAYER = "L0_entry"
MOVES = "setup_s"


def read(run):
    return readers.span(run, "init_s")
