"""Device self time on instructions of the Mamba-2 mixer — scope
``body/mamba`` and its parts ``in_proj``, ``conv``, ``ssd``, ``gate_norm``,
``out_proj`` — over busy time, percent.  The notes give each part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items()
             if k == "body/mamba" or k.startswith("body/mamba/")}
    if not parts:
        run.notes.append("no instruction of scope 'body/mamba' in the trace")
        return None
    busy = run.trace["busy_s"]
    run.notes.append("body/mamba by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
