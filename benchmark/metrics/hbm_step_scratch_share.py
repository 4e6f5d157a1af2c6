"""The step program's scratch: the fullest chip's ``reserved`` — what the
runtime holds outside ``in_use`` for the loaded programs' temporaries — at
the program's point ``step_loaded``, over the limit, percent.  Gauge
``hbnlp_hbm_bytes{point="step_loaded", kind="reserved"}``.  The note gives
it against ``reservable_limit`` (the most the reservation may reach beside
what is in use: the bound a larger step meets first) and the largest free
block, and what ``in_use`` grew by since ``state_ready`` (the loaded
programs' own code, 52-299 MB a step program on a v5e, placed batches, the
step's outputs): state share + scratch share + that growth = the footprint
share."""
from ..lib import memory_readers

LAYER = "L5_device"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = memory_readers.hbm_share(run, "step_loaded", ("reserved",))
    if value is None:
        return None
    hbm = memory_readers.hbm
    reserved = hbm(run, "step_loaded", "reserved")
    reservable = hbm(run, "step_loaded", "reservable_limit")
    free = hbm(run, "step_loaded", "largest_free_block")
    limit = hbm(run, "step_loaded", "limit")
    before = hbm(run, "state_ready", "in_use")
    after = hbm(run, "step_loaded", "in_use")
    if None not in (reservable, free, before, after):
        run.notes.append(
            f"step scratch {int(reserved)} bytes = "
            f"{100.0 * reserved / reservable:.2f}% of reservable_limit "
            f"{int(reservable)}; largest free block {int(free)} = "
            f"{100.0 * free / limit:.2f}% of the limit; in_use grew "
            f"{int(after - before)} bytes = "
            f"{100.0 * (after - before) / limit:.2f}% of the limit between "
            f"state_ready and step_loaded")
    return value
