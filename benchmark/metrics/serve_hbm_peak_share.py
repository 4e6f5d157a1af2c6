"""The fullest chip's footprint (``memory_peak_bytes``: live buffers plus the
runtime's reservation for the loaded programs, ``lib/result.py``) over the
capacity ``memory_stats()`` reports, percent.  The serving cells' twin of
``hbm_peak_share``: a per-layer metric is reported only where the metric it
moves is."""
from .hbm_peak_share import read  # noqa: F401 — the same reader

LAYER = "L5_device"
MOVES = "serve_tokens_per_sec"
