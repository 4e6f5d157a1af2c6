"""Median admission-to-first-generated-token, from the server's own histogram
over the window (the HTTP API returns whole completions, so a client cannot
see it)."""
from ..lib import readers

LAYER = "L1_scheduler"
MOVES = "serve_latency_p50_ms"


def read(run):
    return readers.histogram_quantile_ms(run, "hbnlp_serve_ttft_seconds", 0.5)
