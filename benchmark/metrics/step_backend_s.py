"""Seconds in ``compile_or_get_cached`` for the train step: XLA's compile, or
the load of the executable from the persistent cache (jax 0.9.0 times both
under the ``backend`` phase of ``hbnlp_compile_seconds_total``)."""
from ..lib import program_readers

LAYER = "L0_entry"
MOVES = "setup_s"


def read(run):
    return program_readers.step_compile_seconds(run, ("backend",))
