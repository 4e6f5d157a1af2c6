"""Seconds jax spent tracing the train step and lowering it to StableHLO
(``hbnlp_compile_seconds_total``, phases ``trace`` and ``lower``, function
of the cell's ``programs.step``)."""
from ..lib import program_readers

LAYER = "L0_entry"
MOVES = "setup_s"


def read(run):
    return program_readers.step_compile_seconds(run, ("trace", "lower"))
