"""What compressed convolutional attention adds AROUND the kernel — scopes
``body/cca/qk_mean``, ``conv``, ``qk_norm``, ``rope`` and ``value_shift``:
the q-k mean, the two causal convolutions over the packed latent, the unit
normalisation with the key temperature, the rotary embedding and the value
shift, as XLA (or a kernel) runs them — over the device's busy time,
percent.  The notes give each part."""
from ..lib import program_readers, readers

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"
PARTS = ("qk_mean", "conv", "qk_norm", "rope", "value_shift")


def mix_seconds(run):
    """``{scope: seconds}`` of the mixing scopes, or None where the trace
    holds none."""
    scopes = program_readers.scope_seconds(run)
    if scopes is None:
        return None
    parts = {f"body/cca/{p}": scopes[f"body/cca/{p}"] for p in PARTS
             if f"body/cca/{p}" in scopes}
    if not parts:
        run.notes.append("no instruction of scopes 'body/cca/"
                         + "|".join(PARTS) + "' in the trace")
        return None
    return parts


def read(run):
    parts = mix_seconds(run)
    if parts is None:
        return None
    busy = run.trace["busy_s"]
    run.notes.append("cca mixing by part: " + ", ".join(
        f"{k} {100 * v / busy:.2f}%" for k, v in sorted(parts.items())))
    return readers.share(sum(parts.values()), busy)
