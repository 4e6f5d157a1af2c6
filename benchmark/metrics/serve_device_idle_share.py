"""One minus the union of the device's operation intervals over the traced
window, percent.  The serving cells' twin of ``device_idle_share``: a
per-layer metric is reported only where the metric it moves is."""
from .device_idle_share import read  # noqa: F401 — the same reader

LAYER = "L5_device"
MOVES = "serve_latency_p50_ms"
