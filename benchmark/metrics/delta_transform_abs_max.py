"""The program's gauge ``hbnlp_delta_transform_abs_max``: the largest
magnitude in any chunk's solved transform ``T = (I + strict_tril(diag(beta)
(K K^T o Gamma)))^-1 diag(beta)``, over all gated delta-rule layers of the
newest step the program had read when the run ended.  ``T`` is solved in
float32 and then multiplies ``K`` and ``V`` as a bfloat16 operand: the larger
its entries, the more of the result's precision the cancellation between them
costs."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    return program_readers.counter(run, "hbnlp_delta_transform_abs_max")
