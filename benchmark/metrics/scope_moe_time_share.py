"""Device self time on instructions of the routed expert layer — scope
``body/moe`` and its parts ``router``, ``dispatch``, ``experts``,
``combine`` — over busy time, percent.  The notes give each part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items()
             if k == "body/moe" or k.startswith("body/moe/")}
    if not parts:
        run.notes.append("no instruction of scope 'body/moe' in the trace")
        return None
    busy = run.trace["busy_s"]
    run.notes.append("body/moe by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
