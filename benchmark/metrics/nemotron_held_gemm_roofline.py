"""The held LatentMoE experts' grouped matmuls against their roofline,
percent: the least time the chip could take for the operations and bytes one
step REQUIRES of them (``roofline/nemotron_costs.held_gemm_cost``: TWO
matmuls a pair at ``latent x width``, forward and backward over the pairs
really routed to the held experts, the held experts' weights; what the memory
strategy recomputes is not credited) over the device time of scope
``body/moe/experts``.

The pairs are the program's own count: ``hbnlp_moe_held_pairs_total`` over
``hbnlp_moe_routed_pairs_total`` (both from the same finished steps) times
the pairs a layer routes a step, the mean over the run.  The scope's time
holds the grouped matmuls of all passes plus the replayed forward and the
activation between them, and the floor counts neither."""
import re

from ..lib import program_readers, readers
from ..roofline import costs, nemotron_costs

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"


def read(run):
    scopes = program_readers.scope_seconds(run)
    if scopes is None or "body/moe/experts" not in scopes \
            or not run.config.get("moe_latent_width"):
        return None
    held = program_readers.counter(run, "hbnlp_moe_held_pairs_total")
    routed = program_readers.counter(run, "hbnlp_moe_routed_pairs_total")
    rx = re.compile(run.cell.spec["programs"]["step"])
    steps = sum(len(ds) for name, ds in run.trace["modules"].items()
                if rx.search(name))
    layers = nemotron_costs.count(run.config, "sparse")
    if not held or not routed or not steps or not layers:
        return None
    config = run.config
    pairs = held / routed * config["train_batch_size"] \
        * config["sequence_length"] * config["moe_top_k"]
    flops, bytes_ = nemotron_costs.held_gemm_cost(config, pairs)
    floor, bound = costs.least_seconds(
        flops, bytes_, costs.peaks(run.result.device["kind"]))
    took = scopes["body/moe/experts"]
    run.notes.append(
        f"held latent expert matmuls: {steps} steps x {layers} layers, "
        f"{pairs:.1f} pairs a layer a step (mean of the run), "
        f"{flops / 1e12:.4f} TFLOP and {bytes_ / 1e9:.4f} GB a layer a "
        f"step, {bound}-bound floor {floor * 1e3:.4f} ms; scope "
        f"body/moe/experts took {took / steps / layers * 1e3:.4f} ms a "
        f"layer a step")
    return readers.share(floor * steps * layers, took)
