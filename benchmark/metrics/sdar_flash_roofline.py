"""The block-diffusion mask's attention, its share of its roofline, percent:
the ``flash_*_blockdiff`` kernels' calls, each costed at the LIVE pairs of the
WHOLE mask at 128 / 128 (``roofline/sdar_costs.py flash_cost``: two
block-causal triangles and the noised blocks' own pairs, whatever form scores
them), over the time the kernels took PLUS the time of what the program's
form leaves to XLA round them (scopes ``body/attention/own_block`` — a
query's own block as a ``[L / B, B, B]`` product — and
``body/attention/lse_merge``): the share reads the same work whatever
implements it.  The least time the chip could take for all calls (the larger
of required operations over the peak FLOP/s and bytes over the peak bytes/s):
the forward once a layer a step where the stash keeps ``(out, lse)``, twice
where it does not, the backward (fused, or the dq and dk/dv pair) once.  It
cannot pass 100: the kernels run at least the far pairs' matmuls (the dead
parts of their diagonal tiles on top) and move at least the counted tensors
once, and the own pairs' time is in the denominator."""
from ..lib import program_readers, readers
from ..roofline import costs, sdar_costs
from ..trace import reduce as reduce_mod

LAYER = "L4_kernels"
MOVES = "train_tokens_per_sec_chip"
KERNELS = r"^flash_.*blockdiff"
BESIDE = ("body/attention/own_block", "body/attention/lse_merge")


def read(run):
    if run.trace is None or not run.config.get("diffusion_block"):
        return None
    kinds = reduce_mod.kernel_stats(run.trace, KERNELS)
    if not kinds:
        return None
    peak = costs.peaks(run.result.device["kind"])
    # every layer of the cut holds the same head counts
    layer = sdar_costs.attention_layers(run.config)[0]
    least = took = 0.0
    for kind, (seconds, calls) in sorted(kinds.items()):
        flops, bytes_ = sdar_costs.flash_cost(kind, layer, run.config)
        floor, bound = costs.least_seconds(flops, bytes_, peak)
        run.notes.append(
            f"{kind}: {calls} calls, {seconds / calls * 1e3:.4f} ms each, "
            f"{flops / 1e9:.3f} GFLOP and {bytes_ / 1e6:.3f} MB a call, "
            f"{bound}-bound floor {floor * 1e3:.4f} ms "
            f"({100 * floor * calls / seconds:.2f}%)")
        least += floor * calls
        took += seconds
    scopes = program_readers.scope_seconds(run) or {}
    beside = sum(scopes.get(scope, 0.0) for scope in BESIDE)
    run.notes.append(
        f"the kernels took {took * 1e3:.3f} ms of the window; the own "
        f"blocks and the merge in XLA {beside * 1e3:.3f} ms more, counted "
        "in the denominator")
    return readers.share(least, took + beside)
