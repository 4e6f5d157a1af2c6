"""Device self time on instructions of layer ``lightning`` — scope
``body/lightning`` and its parts ``in_proj``, ``qk_norm``, ``rope``, ``rule``,
``gate_norm``, ``out_proj`` — over busy time, percent.  The notes give each
part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def parts_of(run, prefix: str):
    """``{scope: seconds}`` of ``prefix`` and everything below it, or None."""
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items()
             if k == prefix or k.startswith(prefix + "/")}
    if not parts:
        run.notes.append(f"no instruction of scope {prefix!r} in the trace")
        return None
    busy = run.trace["busy_s"]
    run.notes.append(f"{prefix} by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return parts


def read(run):
    parts = parts_of(run, "body/lightning")
    if parts is None:
        return None
    return readers.share(sum(parts.values()), run.trace["busy_s"])
