"""What the steps of the UNTRACED window took beyond their median interval,
over the window's wall time, percent (the program's step clock, PR 51):
``rate x (1 + share / 100)`` is the rate of the same run without its
stalls.  The note lists the largest with their step and cause."""
from ..lib import step_clock_readers

LAYER = "L1_host_loop"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return step_clock_readers.stall_share(run)
