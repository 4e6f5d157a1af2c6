"""Share of the UNTRACED window's dispatches that entered with no earlier
step left on the device (the step clock's queue depth 0: the host was
late), percent — the untraced counterpart of ``device_idle_share``."""
from ..lib import step_clock_readers

LAYER = "L1_host_loop"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return step_clock_readers.starved_dispatch_share(run)
