"""Median device time of one run of the engine's chunk program."""
from ..lib import readers

LAYER = "L2_step_programs"
MOVES = "serve_latency_p50_ms"


def read(run):
    return readers.module_median_ms(run, "chunk")
