"""Seconds ``Trainer.init_state`` spends placing the parameters on the device
(``shard_params`` / ``jnp.asarray``): the program's span
``setup/place_params``.  The note gives ``setup/init_wait``, the one
``block_until_ready`` on the new state that ends ``init_state``."""
from ..lib import program_readers

LAYER = "L0_entry"
MOVES = "setup_s"


def read(run):
    wait = program_readers.span_seconds(run, "setup/init_wait")
    if wait is not None:
        run.notes.append(f"setup/init_wait {wait:.4f} s")
    return program_readers.span_seconds(run, "setup/place_params")
