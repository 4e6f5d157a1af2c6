"""Device self time on instructions of LatentMoE's two projections — scopes
``body/moe/latent_down`` (the stream into the latent, before dispatch) and
``body/moe/latent_up`` (the combined rows back, after combine), forward,
recomputed and backward — over busy time, percent.  Nothing to read where
the program folds no such scope."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"
SCOPES = ("body/moe/latent_down", "body/moe/latent_up")


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None or not any(s in scopes for s in SCOPES):
        return None
    busy = run.trace["busy_s"]
    run.notes.append("LatentMoE projections: " + ", ".join(
        f"{s} {100 * scopes.get(s, 0.0) / busy:.2f}%" for s in SCOPES))
    return readers.share(sum(scopes.get(s, 0.0) for s in SCOPES), busy)
