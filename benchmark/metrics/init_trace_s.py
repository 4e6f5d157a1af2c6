"""Seconds of ``Model.init`` that are not initializer calls: the walk of the
whole graph in init mode under ``eval_shape`` (span ``setup/model_init``
minus ``hbnlp_init_values_seconds_total``)."""
from ..lib import program_readers

LAYER = "L0_entry"
MOVES = "setup_s"


def read(run):
    whole = program_readers.span_seconds(run, "setup/model_init")
    values = program_readers.counter(run, "hbnlp_init_values_seconds_total")
    if whole is None or values is None:
        return None
    return whole - values
