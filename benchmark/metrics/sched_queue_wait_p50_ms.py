"""Median seconds between the HTTP child's enqueue and the device loop's
pickup, from the server's own histogram over the window."""
from ..lib import readers

LAYER = "L1_scheduler"
MOVES = "serve_latency_p50_ms"


def read(run):
    return readers.histogram_quantile_ms(
        run, "hbnlp_serve_queue_wait_seconds", 0.5)
