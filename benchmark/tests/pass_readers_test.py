"""The readers of the step's passes (PR 70) over a small synthetic ``Run``:
a reduced trace's ``ops`` beside a planted ``tf_op`` table and a planted
registry.  The shares and the note's rest add up; a ``*_bwd*`` instruction
under a forward path makes the three shares read ``None`` with the reason;
on a run without a trace — the parent's case — every reader returns ``None``
with a note and does not raise."""
import pytest

from benchmark.lib import cell as cell_mod
from benchmark.lib import pass_readers as P
from benchmark.lib import program_readers
from benchmark.lib.result import Result, Run

CELL = "train_32big_mixer_b32"
GPT = "jit(step_fn)/jvp(gpt0)"
BACK = "jit(step_fn)/transpose(jvp(gpt0))"
CKPT = BACK + "/body0/jvp(gpt0)/body0/checkpoint"
#: instruction -> (tf_op or None, seconds, calls)
OPS = {
    "fusion.1": (GPT + "/body0/block0_0_0/mlp_0/dot_general", 10.0, 4),
    "flash_fwd_causal.1": (GPT + "/body0/block0_1_0/attention_0/"
                           "flash_fwd_causal/pallas_call", 5.0, 4),
    "fusion.2": (CKPT + "/rematted_computation/block0_0_0/mlp_0/dot_general",
                 9.0, 4),
    "flash_fwd_causal.2": (CKPT + "/rematted_computation/block0_1_0/"
                           "attention_0/flash_fwd_causal/pallas_call",
                           0.2, 4),
    "fusion.3": (CKPT + "/block0_0_0/mlp_0/dot_general", 20.0, 4),
    "flash_bwd_fused_causal.1": (CKPT + "/block0_1_0/attention_0/"
                                 "flash_bwd_fused_causal/pallas_call",
                                 10.0, 4),
    "fusion.4": (BACK + "/while/body/replay/jvp(block0_0_0)/"
                 "bottleneck_group_linear_0/dot_general", 3.0, 4),
    "fusion.5": (BACK + "/while/body/transpose(replay)/jvp(block0_0_0)/"
                 "bottleneck_group_linear_0/dot_general", 6.0, 4),
    "fusion.6": ("jit(step_fn)/optimizer/mul", 4.0, 4),
    "copy.7": (None, 2.0, 4),
    "fusion.8": ("jit(step_fn)/reduce_sum", 0.8, 4),
}
BUSY = sum(seconds for _, seconds, _ in OPS.values())
GB = 1 << 30


@pytest.fixture
def planted(monkeypatch):
    """The ``tf_op`` table in the place of the wire-format reader's, and a
    registry with the stash gauges and the chip's limit."""
    table = {name: op for name, (op, _, _) in OPS.items() if op}
    monkeypatch.setattr(program_readers, "_tf_ops", lambda path: table)
    P._op_passes.cache_clear()
    registry = {
        P.STASH_BYTES: {"labels": ("kind",), "series": {
            ("attention",): GB // 4, ("dense",): GB // 2, ("experts",): 0}},
        P.STASH_LAYERS: {"labels": ("kind",), "series": {
            ("attention",): 7, ("dense",): 3, ("experts",): 0}},
        "hbnlp_hbm_bytes": {"labels": ("point", "kind"), "series": {
            ("step_loaded", "limit"): 16 * GB,
            ("step_loaded", "in_use"): 9 * GB}}}
    monkeypatch.setattr(program_readers, "snapshot", lambda: registry)
    yield table
    P._op_passes.cache_clear()


def _run(traced: bool = True) -> Run:
    cell = cell_mod.load_cell(CELL)
    result = Result(end_to_end={}, correct=True, checks={}, attempted=0,
                    failed=0, device={"kind": "TPU v5 lite"}, spans={},
                    counters={}, trace_path="planted" if traced else None,
                    trace_window="bench_window")
    reduced = None
    if traced:
        reduced = {
            "busy_s": BUSY,
            "ops": {name: seconds for name, (_, seconds, _) in OPS.items()},
            "calls": {name: calls for name, (_, _, calls) in OPS.items()},
            "labels": {name: name + (" kOutput bf16[8,8]"
                                     if name.startswith("fusion") else "")
                       for name in OPS},
            "modules": {"jit_step_fn": [1.0] * 4}}
    return Run(cell=cell, config=cell.model_config(True), result=result,
               trace=reduced)


def the_passes_add_up_test(planted):
    run = _run()
    got = {which: P.pass_share(run, which)
           for which in ("forward", "backward")}
    got["replay"] = P.replay_share(run)
    assert got["forward"] == pytest.approx(100 * 15.0 / BUSY)
    assert got["replay"] == pytest.approx(100 * 12.2 / BUSY)
    assert got["backward"] == pytest.approx(100 * 36.0 / BUSY)
    optimizer = program_readers.scope_share(run, "optimizer")
    notes = "\n".join(run.notes)
    unmarked = float(notes.split("unmarked ")[1].split("%")[0])
    assert unmarked == pytest.approx(100 * 2.8 / BUSY, abs=1e-3)
    assert sum(got.values()) + optimizer + unmarked == pytest.approx(
        100, abs=0.01)


def the_replay_note_is_what_an_issue_writer_reads_test(planted):
    run = _run()
    P.replay_share(run)
    notes = "\n".join(run.notes)
    # the replay by scope, largest first, from 0.5% of busy time: the
    # replayed flash forward (0.29%) is under it
    assert ("replay by scope (every scope from 0.5% of busy time): "
            f"body/mlp {100 * 9.0 / BUSY:.4f}%, body/bottleneck_group_linear "
            f"{100 * 3.0 / BUSY:.4f}%") in notes
    assert "body/attention" not in notes.split("replay by scope")[1] \
        .split("\n")[0]
    # a kernel family's calls by pass, beside what the rule keeps
    assert (f"kernel flash_fwd_causal: 4 forward ({100 * 5.0 / BUSY:.4f}%) "
            f"+ 4 replay ({100 * 0.2 / BUSY:.4f}%) calls in the window, "
            "4 whole steps") in notes
    assert "kernel flash_bwd_fused_causal: 4 backward" in notes
    assert ("hbnlp_remat_stash_layers by kind: attention 7, dense 3, "
            "experts 0") in notes
    # the unmarked rest by name, and what a fusion's root can hide
    assert "largest unmarked instructions: copy.7" in notes
    assert "(tf_op 'absent')" in notes
    assert "(tf_op 'jit(step_fn)/reduce_sum')" in notes
    fused = sum(s for n, (_, s, _) in OPS.items() if n.startswith("fusion"))
    assert f"fusions hold {100 * fused / BUSY:.4f}% of busy time" in notes
    assert "pass fold: 10 instructions with tf_op folded and 11 summed" \
        in notes
    assert "disagrees" not in notes


def a_backward_kernel_under_a_forward_path_refuses_the_shares_test(planted):
    planted["flash_bwd_fused_causal.1"] = OPS["flash_fwd_causal.1"][0]
    P._op_passes.cache_clear()
    run = _run()
    assert P.pass_share(run, "forward") is None
    assert P.replay_share(run) is None
    assert P.pass_share(run, "backward") is None
    notes = "\n".join(run.notes)
    share = f"{100 * 10.0 / BUSY:.4f}%"
    assert (f"passes: NOT REPORTED — {share} of busy time on kernels named "
            "for one direction folds to the other (limit 1.0%)") in notes
    assert (f"whose pass disagrees with their name, {share} of busy time: "
            f"flash_bwd_fused_causal.1 (forward) {share}") in notes
    # the split is still in the note for whoever looks for the cause
    assert "pass shares of busy time: forward" in notes


def a_small_disagreement_is_noted_and_the_shares_stand_test(planted):
    planted["flash_fwd_causal.2"] = OPS["fusion.3"][0]
    P._op_passes.cache_clear()
    run = _run()
    assert P.replay_share(run) == pytest.approx(100 * 12.0 / BUSY)
    assert "flash_fwd_causal.2 (backward)" in "\n".join(run.notes)
    assert "NOT REPORTED" not in "\n".join(run.notes)


def remat_stash_share_is_the_rules_bytes_over_the_limit_test(planted):
    run = _run(traced=False)
    assert P.remat_stash_share(run) == pytest.approx(100 * 0.75 / 16)
    notes = "\n".join(run.notes)
    assert (f"remat stash by kind: attention {GB // 4} bytes in 7 layers, "
            f"dense {GB // 2} bytes in 3 layers, experts 0 bytes in 0 "
            f"layers; limit {16 * GB} bytes") in notes


@pytest.mark.parametrize("name", ["pass_forward_time_share",
                                  "pass_replay_time_share",
                                  "pass_backward_time_share"])
def without_a_trace_a_reader_says_so_and_reads_nothing_test(planted, name):
    run = _run(traced=False)
    assert cell_mod.load_metric(name).read(run) is None
    assert run.notes == ["passes: the run has no reduced trace"]


def a_program_without_pass_key_reads_nothing_test(planted, monkeypatch):
    """The parent of PR 70 under these files: no ``pass_key`` to import."""
    monkeypatch.setattr(P, "_folds", lambda: None)
    P._op_passes.cache_clear()
    run = _run()
    for name in ("pass_forward_time_share", "pass_replay_time_share",
                 "pass_backward_time_share"):
        assert cell_mod.load_metric(name).read(run) is None
    assert set(run.notes) == {
        "passes: the program has no analysis.cost_ledger.pass_key"}


def a_registry_without_the_gauges_reads_nothing_test(monkeypatch):
    monkeypatch.setattr(program_readers, "snapshot", lambda: {})
    run = _run(traced=False)
    assert cell_mod.load_metric("remat_stash_share").read(run) is None
    assert run.notes == ["MISSING: the program's registry holds no "
                         "hbnlp_remat_stash_bytes"]


def a_trace_without_tf_op_reads_nothing_test(monkeypatch):
    monkeypatch.setattr(program_readers, "_tf_ops", lambda path: None)
    P._op_passes.cache_clear()
    run = _run()
    assert P.pass_share(run, "forward") is None
    assert run.notes == ["passes: the trace's device planes carry no tf_op "
                         "stat"]
    P._op_passes.cache_clear()


@pytest.mark.parametrize("name,way", [
    ("flash_fwd_causal.3", "fwd"), ("flash_bwd_dq_window", "bwd"),
    ("delta_rule_bwd.1", "bwd"), ("mamba_conv_fwd", "fwd"),
    ("map_mixer_bwd_dval_causal.2", "bwd"), ("fusion.12", None),
    ("index_loss_pass.1", None), ("forward_fwdish.1", None)])
def a_name_declares_its_direction_test(name, way):
    assert P.direction(name) == way
