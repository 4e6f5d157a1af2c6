"""Device self time on instructions of the Kimi Delta Attention mixer — scope
``body/kda`` and its parts ``in_proj``, ``conv``, ``decay``, ``rule``,
``gate_norm``, ``out_proj`` — over busy time, percent.  The notes give each
part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items()
             if k == "body/kda" or k.startswith("body/kda/")}
    if not parts:
        run.notes.append("no instruction of scope 'body/kda' in the trace")
        return None
    busy = run.trace["busy_s"]
    run.notes.append("body/kda by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
