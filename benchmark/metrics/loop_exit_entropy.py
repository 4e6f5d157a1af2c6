"""The program's gauge ``hbnlp_loop_exit_entropy``: the mean over the tokens
of the entropy of a looped model's exit distribution at the newest step the
program had read when the run ended, nats (``ln loop_steps`` at uniform, 0
where every token leaves after one pass).  The notes give the share of the
tokens' probability that leaves after each pass (``hbnlp_loop_exit_share``)
and each pass's mean cross-entropy (``hbnlp_loop_pass_loss``)."""
from ..lib import program_readers

LAYER = "L3_model_graph"
MOVES = "train_tokens_per_sec_chip"


def _by_pass(metric: str):
    series = program_readers.snapshot().get(metric, {}).get("series", {})
    return [f"{float(value):.4f}" for _, value in sorted(
        series.items(), key=lambda item: int(item[0][0]))]


def read(run):
    value = program_readers.counter(run, "hbnlp_loop_exit_entropy")
    if value is None:
        return None
    run.notes.append(
        "exit share a pass " + " ".join(_by_pass("hbnlp_loop_exit_share"))
        + "; cross-entropy a pass "
        + " ".join(_by_pass("hbnlp_loop_pass_loss")))
    return value
