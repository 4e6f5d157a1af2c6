"""Device self time on what block-diffusion training runs ROUND the layers
and the flash kernels — scopes ``denoise/noise`` (the draws), ``denoise/join``
(the two sequences' ids side by side), ``denoise/split`` (the noised half of
the body's output), and of the mask's parts outside the kernels
``body/attention/halves`` (the clean half's keys and values for both halves),
``body/attention/own_block`` and ``body/attention/lse_merge`` — over busy
time, percent; forward and backward.  What the doubling costs beside the
doubled layers themselves.  The notes give each part."""
from ..lib import program_readers, readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"
MASK_PARTS = ("body/attention/halves", "body/attention/own_block",
              "body/attention/lse_merge")


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {k: v for k, v in scopes.items()
             if k == "denoise" or k.startswith("denoise/") or k in MASK_PARTS}
    if not parts:
        run.notes.append("no instruction of scope 'denoise' or of the "
                         "block-diffusion mask's parts in the trace")
        return None
    busy = run.trace["busy_s"]
    run.notes.append("denoise by part: " + ", ".join(
        f"{k} {100 * v / busy:.3f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
