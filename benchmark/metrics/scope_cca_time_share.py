"""Device self time on instructions of compressed convolutional attention —
scope ``body/cca`` (the flash kernels and the K/V repeat) and its parts
``in_proj``, ``qk_mean``, ``conv``, ``qk_norm``, ``rope``, ``value_shift``,
``out_proj`` — over busy time, percent.  The notes give each part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items()
             if k == "body/cca" or k.startswith("body/cca/")}
    if not parts:
        run.notes.append("no instruction of scope 'body/cca' in the trace")
        return None
    busy = run.trace["busy_s"]
    run.notes.append("body/cca by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
