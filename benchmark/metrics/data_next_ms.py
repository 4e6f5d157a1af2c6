"""Median length of the program's span ``data/next`` (the consumer's wait on
the prefetch queue, ``Prefetcher.__next__``) inside the traced window,
milliseconds; the note gives ``data/place`` (``Trainer.place_batch``)."""
from ..lib import program_readers

LAYER = "L1_host_loop"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    place = program_readers.span_median_ms(run, "data/place")
    if place is not None:
        run.notes.append(f"data/place median {place:.4f} ms")
    return program_readers.span_median_ms(run, "data/next")
