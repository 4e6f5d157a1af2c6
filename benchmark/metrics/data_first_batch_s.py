"""Seconds from the prefetcher's construction to the first batch handed to
the consumer: the program's span ``setup/data_first_batch``
(data/inputs.py), from its registry."""
from ..lib import program_readers

LAYER = "L1_host_loop"
MOVES = "setup_s"


def read(run):
    return program_readers.span_seconds(run, "setup/data_first_batch")
