"""The longest step interval of the UNTRACED window, less what the interval
after it gave back, over the window's median (the program's step clock,
PR 51): about 1.0 on a run without a stall."""
from ..lib import step_clock_readers

LAYER = "L1_host_loop"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return step_clock_readers.interval_max_over_median(run)
