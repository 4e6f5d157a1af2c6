"""The footprint of the training loop as the PROGRAM read it: the fullest
chip's ``in_use`` + ``reserved`` (live buffers plus the runtime's
reservation for the loaded step's temporaries) at the program's point
``step_loaded`` — the first step has run, the loop holds its state and its
batches — over the limit, percent.  Gauge ``hbnlp_hbm_bytes{point=
"step_loaded"}``.  The note lays it beside the harness's own
``memory_peak_bytes`` of the same run, which an untraced run reads at the
end of its window and a traced run after the traced window has donated the
train state."""
from ..lib import memory_readers

LAYER = "L5_device"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = memory_readers.hbm_share(run, "step_loaded",
                                     ("in_use", "reserved"))
    harness = run.result.device.get("memory_peak_bytes")
    limit = run.result.counters.get("memory_limit_bytes")
    if value is not None and harness and limit:
        run.notes.append(
            f"the harness's memory_peak_bytes of this run: {harness} = "
            f"{100.0 * harness / limit:.4f}% of {limit}")
    return value
