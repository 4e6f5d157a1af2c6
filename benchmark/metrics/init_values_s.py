"""Seconds in the parameters' initializers (host numpy normal / QR): the
program's ``hbnlp_init_values_seconds_total``; the note gives how many values
were made."""
from ..lib import program_readers

LAYER = "L0_entry"
MOVES = "setup_s"


def read(run):
    made = program_readers.counter(run, "hbnlp_init_values_total")
    if made is not None:
        run.notes.append(f"{int(made)} parameter values made")
    return program_readers.counter(run, "hbnlp_init_values_seconds_total")
