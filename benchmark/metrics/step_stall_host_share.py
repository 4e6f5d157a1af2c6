"""The part of ``step_stall_share`` whose cause the step clock puts down to
the host (``compile``, ``dispatch``, ``descheduled``, ``data``, ``gc``,
``log_or_save``, ``host_other``), percent of the window; the rest is
``device``: the host was waiting the whole time."""
from ..lib import step_clock_readers

LAYER = "L1_host_loop"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return step_clock_readers.stall_share(run, host_only=True)
