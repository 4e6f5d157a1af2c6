"""Seconds of the warm-up that compiles (or loads from the cache) the cell's
own programs: two train steps, or the requests that build the engine's chunk
programs."""
from ..lib import readers

LAYER = "L0_entry"
MOVES = "setup_s"


def read(run):
    return readers.span(run, "compile_s")
