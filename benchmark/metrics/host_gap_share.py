"""Share of the window's device-idle time that falls inside the harness's own
spans ``data_next`` and ``dispatch``, percent."""
from ..lib import readers

LAYER = "L1_host_loop"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    if run.trace is None:
        return None
    idle = run.trace["idle_by_span"]
    return readers.share(idle.get("data_next", 0.0) + idle.get("dispatch", 0.0),
                         run.trace["idle_s"])
