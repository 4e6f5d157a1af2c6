"""Mesh-aware graft-lint suite (analysis/mesh_audit.py).

The repo at HEAD lowers every available parallel strategy on the 8-device
CPU mesh and passes all three pass families against the committed
``budgets.json`` ``meshes`` section; each pass is then proven to BITE:

* collective budgets — a synthetic surplus all-gather must be flagged
  WITH the mesh axis it reshards over named in the finding;
* sharding specs — a synthetic replicated entry parameter AND a REAL
  lowering with the layout rule broken (``layout_override`` un-mapping
  'heads') must both be flagged as silent replication;
* HBM liveness — a synthetic over-budget walk must be flagged.

Plus: replica-group -> mesh-axis attribution (explicit, iota, transposed
iota, permute pairs), the liveness walk on a hand-checked module, the
budgets-keys exactness contract (stale/orphan rows fail), and the
``mesh-axis-literal`` AST rule.
"""
from __future__ import annotations

import copy
import dataclasses
import json

import pytest

from homebrewnlp_tpu.analysis import ast_lint, hlo_lint, mesh_audit

pytestmark = pytest.mark.staticanalysis


# ---- replica-group / census parsing (pure) ---------------------------------

def replica_group_axes_test():
    mesh = {"data": 4, "model": 2}
    # explicit groups: members differ along 'data' (id = data*2 + model)
    assert hlo_lint.group_axes([[0, 2, 4, 6], [1, 3, 5, 7]],
                               mesh) == ("data",)
    # iota: [4,2]<=[8] -> {0,1},{2,3},... = 'model'
    assert hlo_lint.group_axes(
        hlo_lint._parse_replica_groups("[4,2]<=[8]"), mesh) == ("model",)
    # transposed iota: [2,4]<=[4,2]T(1,0) -> {0,2,4,6},{1,3,5,7} = 'data'
    assert hlo_lint.group_axes(
        hlo_lint._parse_replica_groups("[2,4]<=[4,2]T(1,0)"),
        mesh) == ("data",)
    # a global group spans both
    assert hlo_lint.group_axes([[0, 1, 2, 3, 4, 5, 6, 7]],
                               mesh) == ("data", "model")


def collective_inventory_axes_and_bytes_test():
    """The shared census: counts match collective_census conventions
    (async pairs once), bytes follow the result-shape rules, axes come
    from replica groups / permute pairs."""
    hlo = "\n".join([
        "%ar = f32[4,16]{1,0} all-reduce(f32[4,16]{1,0} %x), "
        "replica_groups={{0,2,4,6},{1,3,5,7}}",
        "%ag = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %y), "
        "replica_groups=[4,2]<=[8]",
        "%agd = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ag)",
        "%cp = f32[4]{0} collective-permute(f32[4]{0} %z), "
        "source_target_pairs={{0,2},{2,4},{4,6},{6,0}}",
    ])
    inv = hlo_lint.collective_inventory(hlo, {"data": 4, "model": 2})
    assert inv["all-reduce"] == {"count": 1, "bytes": 256,
                                 "axes": {"data": 1}}
    # async pair counted ONCE; -start bytes = the LARGEST tuple member
    assert inv["all-gather"] == {"count": 1, "bytes": 32,
                                 "axes": {"model": 1}}
    assert inv["collective-permute"]["axes"] == {"data": 1}
    # counting conventions agree with the plain census by construction
    census = hlo_lint.collective_census(hlo)
    assert {k: v["count"] for k, v in inv.items()} \
        == {k: v for k, v in census.items() if v}
    # a combined all-reduce: XLA numbers a long tuple's elements, and the
    # "=" of the comment must not cut the result short (it read 24 of 40)
    combined = ("%c = (f32[2]{0}, f32[2]{0}, f32[2]{0}, f32[2]{0}, "
                "/*index=5*/f32[2]{0}) all-reduce(%a, %b, %c, %d, %e), "
                "replica_groups=[4,2]<=[8]")
    assert hlo_lint.collective_inventory(combined)["all-reduce"] \
        == {"count": 1, "bytes": 40}


# ---- pass 1 negative control: surplus collective names its axis ------------

def mesh_collective_surplus_names_axis_test():
    budget = {"all-gather": {"count": 1, "bytes": 32,
                             "axes": {"data": 1}}}
    fresh = {"all-gather": {"count": 3, "bytes": 96,
                            "axes": {"data": 1, "model": 2}}}
    findings = mesh_audit.mesh_collective_budget_audit("e", fresh, budget)
    assert findings and findings[0].rule == "mesh-collective"
    assert "mesh axis 'model' (+2)" in findings[0].message
    # within tolerance passes
    assert mesh_audit.mesh_collective_budget_audit("e", budget, budget) == []
    # a NEW collective kind (budget 0) always fails
    novel = {"all-to-all": {"count": 2, "bytes": 64,
                            "axes": {"model": 2}}}
    findings = mesh_audit.mesh_collective_budget_audit("e", novel, {})
    assert findings and "all-to-all" in findings[0].message
    # a large DROP is also a finding (the comms pattern changed)
    gone = {"all-gather": {"count": 0, "bytes": 0}}
    assert mesh_audit.mesh_collective_budget_audit("e", gone, budget)


# ---- pass 2 negative control: mis-sharded protected leaf -------------------

_ENTRY_HLO = "\n".join([
    "HloModule jit_step",
    "",
    "ENTRY %main.1_spmd (p0: f32[4,2,16], p1: s32[1,16,1]) -> f32[] {",
    "  %param.0 = f32[4,2,16]{2,1,0} parameter(0), sharding={replicated}, "
    "metadata={op_name=\"state.variables['blk/w']\"}",
    "  %param.1 = s32[1,16,1]{2,1,0} parameter(1), "
    "sharding={devices=[4,1,1,2]<=[8] last_tile_dim_replicate}, "
    "metadata={op_name=\"batch['token_x']\"}",
    "  ROOT %c = f32[] constant(0)",
    "}",
])

_PROTECTED = {
    "blk/w": {"kind": "exact", "full": "f32[4,2,16]",
              "shard": "f32[4,1,16]", "axes": ["model"]},
    "token_x": {"kind": "exact", "full": "s32[4,16,1]",
                "shard": "s32[1,16,1]", "axes": ["data"]},
}


def sharding_spec_replicated_leaf_test():
    """'blk/w' rides the entry at FULL shape -> silent replication is
    flagged (and names the contract axis); the correctly-sharded batch
    leaf passes."""
    findings = mesh_audit.sharding_spec_audit("e", _ENTRY_HLO, _PROTECTED)
    assert [f.rule for f in findings] == ["mesh-sharding"]
    msg = findings[0].message
    assert "SILENTLY REPLICATED" in msg and "blk/w" in msg \
        and "model" in msg
    # the same module against a contract it satisfies is clean
    ok = {"token_x": _PROTECTED["token_x"]}
    assert mesh_audit.sharding_spec_audit("e", _ENTRY_HLO, ok) == []


def sharding_spec_full_gather_test():
    """A compiler-inserted all-gather materialising a sharded leaf at
    full shape is flagged — unless it is in the committed baseline
    (``gather_ok_shapes``)."""
    hlo = _ENTRY_HLO.replace(
        "  ROOT %c = f32[] constant(0)",
        "  %ag = f32[4,2,16]{2,1,0} all-gather(f32[4,1,16]{2,1,0} %x), "
        "replica_groups=[4,2]<=[8]\n"
        "  ROOT %c = f32[] constant(0)")
    # make the leaf itself correctly sharded so ONLY the gather fires
    hlo = hlo.replace("f32[4,2,16]{2,1,0} parameter(0)",
                      "f32[4,1,16]{2,1,0} parameter(0)")
    protected = {"blk/w": _PROTECTED["blk/w"]}
    findings = mesh_audit.sharding_spec_audit("e", hlo, protected)
    assert findings and "all-gather" in findings[0].message \
        and "blk/w" in findings[0].message
    assert mesh_audit.sharding_spec_audit(
        "e", hlo, protected, gather_allow=("f32[4,2,16]",)) == []


def sharding_spec_missing_leaf_test():
    """A protected leaf that vanished from the entry parameters is a loud
    finding, not a silent skip."""
    findings = mesh_audit.sharding_spec_audit(
        "e", _ENTRY_HLO, {"gone/leaf": {"kind": "exact",
                                        "full": "f32[8,8]",
                                        "shard": "f32[8,4]",
                                        "axes": ["model"]}})
    assert findings and "not found" in findings[0].message


def sharding_spec_real_broken_layout_test():
    """REAL negative control: dp_tp lowered with the layout rule broken
    (``layout_override`` un-maps 'heads') compiles params replicated; the
    strategy contract still demands heads-over-'model', so the audit must
    flag silent replication on real compiled HLO, not only on synthetic
    text."""
    base = mesh_audit.MESH_STRATEGIES["dp_tp"]
    broken = dataclasses.replace(
        base, name="dp_tp_broken", entries=("train_step",),
        overrides={**base.overrides, "layout_override": {"heads": None}})
    hlo, ctx = mesh_audit.lower_train_under_mesh(broken)
    findings = mesh_audit.sharding_spec_audit("dp_tp_broken/train_step",
                                              hlo, ctx["protected"])
    assert any("SILENTLY REPLICATED" in f.message for f in findings), \
        [str(f) for f in findings]


# ---- pass 3 negative control: HBM-budget overflow --------------------------

_WALK_HLO = "\n".join([
    "HloModule m",
    "",
    "%helper (hp: f32[2]) -> f32[2] {",
    "  %hp = f32[2]{0} parameter(0)",
    "  %big = f32[100]{0} broadcast(f32[2]{0} %hp)",
    "  ROOT %r = f32[2]{0} slice(f32[100]{0} %big)",
    "}",
    "",
    "ENTRY %main (p0: f32[4]) -> f32[4] {",
    "  %p0 = f32[4]{0} parameter(0)",
    "  %t1 = f32[8]{0} broadcast(f32[4]{0} %p0)",
    "  %t2 = f32[8]{0} negate(f32[8]{0} %t1)",
    "  ROOT %out = f32[4]{0} slice(f32[8]{0} %t2)",
    "}",
])


def liveness_walk_hand_checked_test():
    """args=16B; t1 (32B) allocs, t2 (32B) allocs then t1 frees (last use
    was t2's line), out (16B) allocs while t2 live -> temp peak
    16 + 64 = 80 total at the t2 line; out line: t2 (32) + out (16) + args
    = 64.  Peak = args + max concurrent temps = 16 + 64 = 80."""
    est = mesh_audit.liveness_estimate(_WALK_HLO)
    assert est["args_bytes"] == 16
    assert est["peak_bytes"] == 80, est
    assert est["temp_peak_bytes"] == 64


def liveness_callee_peak_test():
    """A called computation's internal temporaries stack on the caller's
    live set at the call site."""
    hlo = _WALK_HLO.replace(
        "  %t2 = f32[8]{0} negate(f32[8]{0} %t1)",
        "  %t2 = f32[8]{0} call(f32[8]{0} %t1), to_apply=%helper")
    est = mesh_audit.liveness_estimate(hlo)
    # helper's internal big broadcast = 400B + its root slice 8B
    assert est["peak_bytes"] > 80 + 400 - 8, est


def hbm_liveness_over_budget_test():
    est = {"peak_bytes": 2000, "args_bytes": 1000, "temp_peak_bytes": 1000}
    committed = {"peak_bytes": 1000}
    findings = mesh_audit.hbm_liveness_audit("e", est, committed,
                                             hbm_bytes=10 ** 9)
    assert findings and findings[0].rule == "mesh-liveness"
    assert "OOM" in findings[0].message
    # within tolerance passes
    assert mesh_audit.hbm_liveness_audit(
        "e", est, {"peak_bytes": 1950}, hbm_bytes=10 ** 9) == []
    # absolute per-chip HBM overflow fails even with a matching budget
    findings = mesh_audit.hbm_liveness_audit(
        "e", est, {"peak_bytes": 2000}, hbm_bytes=1500)
    assert findings and "per-chip HBM" in findings[0].message


# ---- budgets-keys exactness (stale/orphan rows fail) -----------------------

def budgets_keys_exact_at_head_test():
    assert mesh_audit.budget_coverage_audit() == []


def budgets_stale_rows_fail_test():
    budgets = copy.deepcopy(hlo_lint.load_budgets())
    budgets["entry_points"]["renamed_step"] = {"all-reduce": 0}
    budgets["meshes"]["dropped_strategy"] = {"mesh": {}, "entries": {}}
    del budgets["meshes"]["ring_sp"]
    findings = mesh_audit.budget_coverage_audit(budgets)
    msgs = "\n".join(str(f) for f in findings)
    assert "renamed_step" in msgs          # orphan entry row
    assert "dropped_strategy" in msgs      # orphan mesh row
    assert "ring_sp" in msgs               # missing registered strategy
    assert all(f.rule == "mesh-budget-keys" for f in findings)


def budgets_stale_entry_within_strategy_fails_test():
    budgets = copy.deepcopy(hlo_lint.load_budgets())
    row = budgets["meshes"]["dp_tp"]["entries"]
    row["prefill_entry_step"] = dict(row["train_step"])  # orphan entry
    del row["decode_chunk_step"]                          # missing entry
    findings = mesh_audit.budget_coverage_audit(budgets)
    msgs = "\n".join(str(f) for f in findings)
    assert "prefill_entry_step" in msgs and "decode_chunk_step" in msgs


def committed_strategy_that_stops_lowering_fails_test():
    """A strategy with committed NON-pending budgets that env-gap-skips is
    a finding (the lint must not stay green while its budgets audit
    nothing); a row whose ``pending`` marker agrees with the skip stays a
    legitimate, loudly-printed skip."""
    findings = mesh_audit.audit_lowered_meshes(
        {}, {"ring_sp": "PartitionId instruction is not supported"})
    assert any(f.rule == "mesh-lowering" and "ring_sp" in f.entry
               for f in findings), [str(f) for f in findings]
    # the pp_* rows carry pending markers, so their skips stay clean
    findings = mesh_audit.audit_lowered_meshes(
        {}, {"pp_gpipe": "PartitionId instruction is not supported"})
    assert not any(f.rule == "mesh-lowering" for f in findings)


def analytic_floor_refuses_degenerate_write_test():
    """--write must refuse a train-step budget whose census shows the
    strategy is not actually parallel (no grad all-reduce)."""
    strategy = mesh_audit.MESH_STRATEGIES["dp_tp"]
    ctx = {"mesh_shape": {"data": 4, "model": 2}, "param_bytes": 10000,
           "protected": {}}
    row = {"collectives": {}}
    with pytest.raises(ValueError, match="not actually parallel"):
        mesh_audit._write_gate(strategy, "train_step", ctx, row)
    # collectives over a foreign axis are refused as resharding
    row = {"collectives": {
        "all-reduce": {"count": 5, "bytes": 10000,
                       "axes": {"data": 4, "sequence": 1}}}}
    with pytest.raises(ValueError, match="resharding"):
        mesh_audit._write_gate(strategy, "train_step", ctx, row)


# ---- the mesh-axis-literal AST rule ----------------------------------------

def mesh_axis_literal_rule_test():
    bad = ("from jax.sharding import PartitionSpec\n"
           "spec = PartitionSpec('model', None)\n")
    findings = ast_lint.lint_source("homebrewnlp_tpu/model/new.py", bad)
    assert [f.rule for f in findings] == ["mesh-axis-literal"]
    assert '"model"' in findings[0].message
    # mesh.shape subscripts / .get keys and axis_names membership count
    for snippet in ("n = mesh.shape['pipe']\n",
                    "n = mesh.shape.get('data', 1)\n",
                    "ok = 'sequence' in mesh.axis_names\n"):
        assert [f.rule for f in
                ast_lint.lint_source("homebrewnlp_tpu/x.py", snippet)] \
            == ["mesh-axis-literal"], snippet


def mesh_axis_literal_scope_test():
    """Only axis-consuming positions are flagged: dim names, dict
    literals, and unrelated strings stay out of scope; the axis-defining
    layers are exempt; the suppression marker works."""
    for ok in ("d = Dim('sequence', 8)\n",
               "cfg = {'data': 4, 'model': 2}\n",
               "mode = 'model'\n",
               "x = other.shape['data']\n"):  # not a mesh expression
        assert ast_lint.lint_source("homebrewnlp_tpu/x.py", ok) == [], ok
    exempt = "spec = PartitionSpec('model')\n"
    assert ast_lint.lint_source(
        "homebrewnlp_tpu/parallel/ring_attention.py", exempt) == []
    assert ast_lint.lint_source("homebrewnlp_tpu/core/sharding.py",
                                exempt) == []
    assert ast_lint.lint_source("homebrewnlp_tpu/config.py", exempt) == []
    marked = ("spec = PartitionSpec('model')  "
              "# graft-lint: allow[mesh-axis-literal]\n")
    assert ast_lint.lint_source("homebrewnlp_tpu/x.py", marked) == []


def mesh_axis_names_pinned_to_shardlib_test():
    """The rule's mirrored axis set stays in sync with the canonical
    constants (mirrored, not imported: ast_lint must import without
    jax)."""
    from homebrewnlp_tpu.core import sharding as shardlib
    assert ast_lint.MESH_AXIS_NAMES == frozenset(shardlib.MESH_AXES)


# ---- the repo at HEAD is clean ---------------------------------------------

@pytest.fixture(scope="module")
def lowered_strategies():
    """ONE lowering of every available strategy shared by the module — the
    head-clean audit and the budgets-reproduce check read the same
    compiles, like graft_lint --mesh does."""
    return mesh_audit.lower_strategies()


def mesh_audit_head_clean_test(lowered_strategies):
    """Every strategy the environment can lower passes all three pass
    families against the committed budgets; skips are ONLY the classified
    environment gaps (mesh_audit._ENV_GAP_MARKERS), never silent."""
    lowered, skipped = lowered_strategies
    findings = mesh_audit.audit_lowered_meshes(lowered, skipped)
    assert findings == [], "\n".join(str(f) for f in findings)
    lowerable = set(mesh_audit.MESH_STRATEGIES) - set(skipped)
    # dp_tp, ring_sp, moe_ep lower on every rig this repo supports; the
    # pipeline strategies abort XLA:CPU on jax 0.9.0 (probed out of process)
    assert {"dp_tp", "ring_sp", "moe_ep"} <= lowerable, skipped
    for reason in skipped.values():
        assert any(m in reason for m in mesh_audit._ENV_GAP_MARKERS)


def committed_budgets_match_fresh_lowering_test(lowered_strategies):
    """The committed meshes section reproduces from a fresh lowering (the
    same bit-for-bit census the --write protocol would emit), so a stale
    commit cannot hide behind tolerance."""
    lowered, skipped = lowered_strategies
    fresh = mesh_audit.build_mesh_budgets(lowered, skipped)
    stored = hlo_lint.load_budgets()["meshes"]
    for name in lowered:
        a = json.dumps(fresh[name]["entries"], sort_keys=True)
        b = json.dumps(stored[name]["entries"], sort_keys=True)
        assert a == b, f"{name} budgets drifted from HEAD"
