"""The fullest chip's footprint (``memory_peak_bytes``: live buffers plus the
runtime's reservation for the loaded programs, ``lib/result.py``) over the
capacity ``memory_stats()`` reports, percent."""
from ..lib import readers

LAYER = "L5_device"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return readers.share(run.result.device.get("memory_peak_bytes"),
                         run.result.counters.get("memory_limit_bytes"))
