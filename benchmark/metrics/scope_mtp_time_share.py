"""Device self time on instructions of the multi-token-prediction module —
scope ``mtp`` and everything below it: ``mtp/join`` (the two norms and ``W_eh``),
``mtp/body/...`` (the module's latent attention and sparse layer, folded as
the body's are), ``mtp/output`` (its last norm), ``mtp/head_loss`` (the second
pass of the chunked head) — over busy time, percent; forward and backward,
the optimizer's update of its parameters not (scope ``optimizer``).  The
notes give each part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items()
             if k == "mtp" or k.startswith("mtp/")}
    if not parts:
        run.notes.append("no instruction of scope 'mtp' in the trace")
        return None
    busy = run.trace["busy_s"]
    run.notes.append("mtp by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
