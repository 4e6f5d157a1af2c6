"""The program's gauge ``hbnlp_denoise_masked_share``: the masked positions
over the trained tokens of the newest step the program had read when the run
ended, percent (near 50: the rates are ``U[diffusion_t_min, 1]`` a block; the
loss is over these positions alone).  The notes carry
``hbnlp_denoise_weight_mean`` (the mean over the trained tokens of the loss's
weight a position, ``m / t``: near 1), ``hbnlp_denoise_stream_positions`` and
``hbnlp_denoise_loss`` (the step's loss in float32)."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    value = program_readers.counter(run, "hbnlp_denoise_masked_share")
    if value is None:
        return None
    run.notes.append(", ".join(
        f"{name} {program_readers.counter(run, name)}" for name in (
            "hbnlp_denoise_weight_mean", "hbnlp_denoise_stream_positions",
            "hbnlp_denoise_loss")))
    return 100.0 * value
