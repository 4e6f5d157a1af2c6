"""Device self time on what the two latents cost round the flash kernels —
the parts ``q_down``, ``q_norm``, ``q_proj``, ``kv_down``, ``kv_norm``,
``kv_up``, ``latent_rope`` and ``out_proj`` of scope ``body/attention`` (layer
0's and the body's latent attention layers; the module's are under ``mtp``
and counted here too, by the same parts) — over busy time, percent.  The
kernels themselves and what names their outputs stay in ``body/attention``.
The notes give each part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"
PARTS = ("q_down", "q_norm", "q_proj", "kv_down", "kv_norm", "kv_up",
         "latent_rope", "out_proj")


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items() if any(
        k == f"{root}/{part}" for root in ("body/attention",
                                           "mtp/body/attention")
        for part in PARTS)}
    if not any(k.endswith("/q_down") for k in parts):
        run.notes.append("no instruction of scope 'body/attention/q_down' "
                         "in the trace: no query latent")
        return None
    busy = run.trace["busy_s"]
    run.notes.append("the latents' projections by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
