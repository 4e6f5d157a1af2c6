"""Device self time on instructions of the ZAYA1 router — scope
``body/moe/router`` (softmax, top-1, the balance term's gradient) and its
parts ``down`` (the projection to the router's width), ``carry`` (the
previous layer's router state) and ``mlp`` — over busy time, percent.  The
notes give each part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items()
             if k.startswith("body/moe/router/")}
    if not parts:
        run.notes.append("no instruction of scopes 'body/moe/router/"
                         "down|carry|mlp' in the trace")
        return None
    parts["body/moe/router"] = scopes.get("body/moe/router", 0.0)
    busy = run.trace["busy_s"]
    run.notes.append("body/moe/router by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
