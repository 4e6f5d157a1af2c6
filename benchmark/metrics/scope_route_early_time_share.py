"""Device self time on the early router — scope ``body/route_early`` (layer
``route_early``'s matmul in the attention block, forward, replay and its two
gradients) and ``body/moe/router/carried`` (where the sparse layer takes the
carried logits and the balance term's gradient enters them) — over busy
time, percent.  The softmax and top-k stay in ``body/moe/router``, as every
sparse cell's.  The notes give each part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"
PARTS = ("body/route_early", "body/moe/router/carried")


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items() if k in PARTS}
    if "body/route_early" not in parts:
        run.notes.append("no instruction of scope 'body/route_early' in the "
                         "trace")
        return None
    busy = run.trace["busy_s"]
    router = scopes.get("body/moe/router", 0.0)
    run.notes.append("the early router by part: " + ", ".join(
        f"{k} {100 * v / busy:.3f}%" for k, v in sorted(parts.items()))
        + f"; body/moe/router {100 * router / busy:.3f}%")
    return readers.share(sum(parts.values()), busy)
