"""Share of the window's device-idle time that falls inside the PROGRAM's own
spans ``train/step_dispatch``, ``data/next`` and ``data/place``, percent:
``host_gap_share`` by the program's names."""
from ..lib import program_readers

LAYER = "L1_host_loop"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.program_gap_share(run)
