"""What the train state takes before any step program is loaded: the fullest
chip's ``in_use`` at the program's point ``state_ready`` (the end of
``Trainer.init_state``: parameters, optimizer slots, the first batch) over
the limit, percent.  Gauge ``hbnlp_hbm_bytes{point="state_ready",
kind="in_use"}``; the note gives ``hbnlp_train_state_bytes{kind}``, the
parameters' and the optimizer slots' bytes on that chip."""
from ..lib import memory_readers

LAYER = "L5_device"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = memory_readers.hbm_share(run, "state_ready", ("in_use",))
    if value is not None:
        parts = {kind: memory_readers.gauge(run, memory_readers.STATE,
                                            kind=kind)
                 for kind in ("params", "opt_slots")}
        run.notes.append("train state on that chip: " + ", ".join(
            f"{kind} {int(v)} bytes" for kind, v in parts.items()
            if v is not None))
    return value
