"""Per-entry, per-scope cost ledger (docs/OBSERVABILITY.md 'Cost
attribution').

The model graph carries ``jax.named_scope`` regions (core/scope.py mirrors
every scope frame into jax's name stack), so both jaxpr equations
(``source_info.name_stack``) and compiled-HLO instructions
(``metadata={op_name=...}``) name the block/layer that produced them.  This
module turns that into a budgeted artifact:

* :func:`build_ledger` — for each entry point in
  ``analysis/entry_points.py``, walk the traced jaxpr with
  ``utils.flops.scope_costs`` (matmul FLOPs + unfused bytes per name
  stack), fold stacks into coarse :func:`scope_key` scopes, attach XLA's
  whole-module ``cost_analysis`` numbers, and classify each scope against
  the ``ROOFLINE_DEVICE`` roofline (compute- vs HBM-bound).
* ``analysis/cost_ledger.json`` — the committed ledger;
  :func:`ledger_audit` regression-checks a fresh build against it the way
  ``budgets.json`` gates collectives (drift beyond ``tolerance`` = lint
  finding; update protocol: ``python -m homebrewnlp_tpu.analysis.cost_ledger
  --write`` and review the diff, docs/STATIC_ANALYSIS.md).
* :func:`scope_key` also folds the ``tf_op`` of a TPU trace's device ops
  (``benchmark/lib/program_readers.py``), so measured time and these
  budgets share their scope names; :func:`pass_key` folds the same path
  into the PASS that runs the instruction — forward, replay, backward,
  optimizer (``benchmark/lib/pass_readers.py``).

Import stays cheap: jax only inside functions (the AST-only consumers of
the package import this module's :func:`scope_key` without jax).
"""
from __future__ import annotations

import json
import os
import re
import typing

from . import hlo_lint

LEDGER_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "cost_ledger.json")

#: the device kind whose roofline classifies scope bounds in the COMMITTED
#: ledger — a fixed reference chip, so the bound column is deterministic
#: across the CPU test rig and TPU runs (utils/flops.py tables; the v5e is
#: the chip the flagship numbers were measured on)
ROOFLINE_DEVICE = "TPU v5e"

#: relative drift in per-scope flops/bytes the regression check tolerates
DEFAULT_TOLERANCE = 0.05

# ---- scope folding ---------------------------------------------------------

_TRANSFORM_RE = re.compile(r"^[A-Za-z_][A-Za-z_0-9]*\((.*)\)$")
_PHASES = ("input", "body", "output", "loss")
#: named-scope markers that name a region directly (model/decode.py,
#: infer/sampler.py, train/__init__.py)
_SPECIAL = {"cache_read": "decode/cache_read",
            "cache_write": "decode/cache_write",
            "sampling": "decode/sampling",
            "optimizer": "optimizer",
            # the head matmul with its cross-entropy (model/__init__.py)
            "head_loss": "head_loss",
            # a looped model's exit gate, its distribution and the weighting
            # of the passes' losses (model/loop.py)
            "exit_gate": "exit_gate"}
#: model/frontend.py LAYER_FUNCTIONS keys (mirrored, not imported — this
#: module must stay importable without jax); update together
_LAYER_NAMES = frozenset((
    "feed_forward", "attention", "cummean", "cumsum", "norm", "rezero",
    "activation", "convolution", "dropout", "group_linear", "split_path",
    "feed_forward_product_key_memory", "product_key_memory",
    "reduced_half_linear", "transpose_sequence_features",
    "bottleneck_group_linear", "sum_heads", "moe", "mamba", "gated_delta",
    "kda", "mlp", "cca", "lightning", "route_early",
    # no layer function: a block part's scaled residual merge
    # (model/frontend.py scaled_merge) opens a scope of its own beside them
    "merge"))
#: the parts of layer ``moe`` (model/moe.py), each a scope of its own below
#: ``body/moe``
_MOE_PARTS = frozenset(("router", "dispatch", "experts", "combine",
                        "shared", "latent_down", "latent_up"))
#: the parts of ZAYA1's router (flag ``router_mlp``) below
#: ``body/moe/router``, and where flag ``routed_early`` takes the logits an
#: earlier block's ``route_early`` layer left (``carried``; that layer's own
#: matmul is ``body/route_early``); the one-matrix router has none
_ROUTER_PARTS = frozenset(("down", "carry", "mlp", "carried"))
#: the parts of layer ``cca`` (model/cca.py) below ``body/cca``; the flash
#: kernels stay in ``body/cca`` itself
_CCA_PARTS = frozenset(("in_proj", "qk_mean", "conv", "qk_norm", "rope",
                        "value_shift", "out_proj"))
#: the standard attention's per-head output gate, and the projections and
#: the rotary of its latent form (flags ``kv_latent<n>``, ``q_latent<n>``,
#: ``rope``; model/spatial.py), each a scope of its own below
#: ``body/attention``; the latent form's ``attend`` (the flash
#: kernels and what names their outputs) stays in ``body/attention`` itself,
#: as ``cca``'s kernels stay in ``body/cca``
_ATTENTION_PARTS = frozenset(("gate", "q_down", "q_norm", "q_proj", "kv_down",
                              "kv_norm", "kv_up", "latent_rope", "out_proj",
                              # what the block-diffusion mask runs round its
                              # kernels (flag ``block_diffusion``): the clean
                              # half's keys for both halves, a query's own
                              # block, the merge by log-sum-exp
                              "halves", "own_block", "lse_merge"))
#: block-diffusion training (model/denoise.py): the draws, the two sequences'
#: ids side by side, the noised half of the body's output — ``denoise/<part>``
#: wherever in the model they run
_DENOISE = "denoise"
_DENOISE_PARTS = frozenset(("noise", "join", "split"))
#: the steps of attention flag ``sparse`` (model/sparse.py; ``attend`` holds
#: the selected kernels) and of flag ``indexed`` (model/indexer.py: ``index``,
#: ``select``, ``attend`` and its own ``index_loss``) below
#: ``body/attention/sparse_attention``
_SPARSE_PARTS = frozenset(("compress", "index", "select", "attend",
                           "index_loss"))
#: the parts of layer ``lightning`` (model/lightning.py) below
#: ``body/lightning``; the rule's own steps (``intra_chunk``,
#: ``chunk_states``, ``inter_chunk``, ``state_out``) stay inside
#: ``body/lightning/rule``
_LIGHTNING_PARTS = frozenset(("in_proj", "qk_norm", "rope", "rule",
                              "gate_norm", "out_proj"))
#: the parts of layer ``mamba`` (model/mamba.py) below ``body/mamba``; the
#: scan's own steps (``intra_chunk``, ``chunk_states``, ``inter_chunk``,
#: ``state_out``) stay inside ``body/mamba/ssd``
_MAMBA_PARTS = frozenset(("in_proj", "conv", "ssd", "gate_norm", "out_proj"))
#: the parts of layer ``gated_delta`` (model/gated_delta.py) below
#: ``body/gated_delta``; the rule's own steps (``decay``, ``solve``,
#: ``intra_chunk``, ``inter_chunk``, ``state_out``) stay inside
#: ``body/gated_delta/delta_rule``
_DELTA_PARTS = frozenset(("in_proj", "conv", "delta_rule", "gate_norm",
                          "out_proj"))
#: the parts of layer ``kda`` (model/kda.py) below ``body/kda``; the rule's
#: own steps (``decay``, ``solve``, ``intra_chunk``, ``inter_chunk``,
#: ``state_out``) stay inside ``body/kda/rule``
_KDA_PARTS = frozenset(("in_proj", "conv", "decay", "rule", "gate_norm",
                        "out_proj"))


def _unwrap(comp: str) -> str:
    """``"transpose(jvp(gpt0))"`` -> ``"gpt0"``; plain names pass through."""
    while True:
        m = _TRANSFORM_RE.match(comp)
        if m is None:
            return comp
        comp = m.group(1)


def _basename(comp: str) -> str:
    """Strip the scope-counter suffix: ``"attention_1"`` -> ``"attention"``,
    ``"body0"`` -> ``"body"``."""
    return comp.rstrip("0123456789").rstrip("_")


#: a multi-token-prediction module (model/mtp.py): everything below scope
#: ``mtp`` folds as it would in the main model, under ``mtp/`` — ``mtp/join``,
#: ``mtp/body/<layer>[/<part>]``, ``mtp/output``, ``mtp/head_loss``
_MTP = "mtp"
_MTP_PARTS = frozenset(("join",))


def scope_key(path: str) -> str:
    """Fold a name-stack / HLO ``op_name`` path into a coarse model scope:
    :func:`_model_scope_key`, and what lies below scope ``mtp`` (a
    multi-token-prediction module, model/mtp.py) folded the same way under
    ``mtp/`` (``mtp/join``, ``mtp/body/attention/q_proj``, ``mtp/head_loss``,
    ..; ``mtp`` itself for what names nothing below it).  The optimizer's
    update of the module's parameters is ``optimizer``'s."""
    comps = str(path).split("/")
    bases = [_basename(_unwrap(comp)) for comp in comps]
    if _MTP not in bases or "optimizer" in bases:
        return _model_scope_key(path)
    below = bases[bases.index(_MTP) + 1:]
    part = next((base for base in below if base in _MTP_PARTS), None)
    if part is not None:
        return f"{_MTP}/{part}"
    key = _model_scope_key("/".join(below))
    return _MTP if key == "unscoped" else f"{_MTP}/{key}"


#: the components that say "this forward runs AGAIN, inside a backward":
#: ``jax.checkpoint``'s own name for the computation it rematerializes
#: (jax ``ad_checkpoint.py``) and the scope under which the program makes its
#: own replays (core/scope.py ``REPLAY``; mirrored, not imported — this module
#: must stay importable without jax; update together)
_REPLAY_MARKS = frozenset(("rematted_computation", "replay"))
PASSES = ("forward", "replay", "backward", "optimizer", "unmarked")


def pass_key(path: str) -> str:
    """Fold a name-stack / HLO ``op_name`` path of the train step into the
    PASS that runs the instruction, one of :data:`PASSES` — the dimension
    :func:`scope_key` unwraps away:

    * ``optimizer`` where :func:`scope_key` says so;
    * ``replay``: a component is one of :data:`_REPLAY_MARKS` — a forward
      made again for a backward — and the marked region was not itself
      transposed;
    * ``backward``: a ``transpose(..)`` wrapper stands and no replay mark
      does; or the LAST mark's region is the transposed one: jax writes that
      as a ``transpose(..)`` on the mark or below it
      (``while/body/transpose(replay)/jvp(block0_0_0)``), or, where the
      region was traced into the enclosing program under its whole stack, as
      that stack wrapped once more
      (``transpose(transpose(jvp(gpt0)))/body0/replay/jvp(block0_0_0)``);
    * ``forward``: no transpose and no mark, under a differentiated region
      (a ``jvp(..)`` wrapper) or any model scope;
    * ``unmarked``: the rest — no path, the step's glue, and an ARGUMENT's
      own name (``state.variables['gpt0/body0/..']``: XLA's copies of a
      parameter carry it, and :func:`scope_key` reads a scope out of it).

    Recomputation INSIDE a kernel (a flash backward forming ``p`` again) is
    its instruction's pass, ``backward``."""
    path = str(path)
    scope = scope_key(path)
    if scope == "optimizer":
        return "optimizer"
    comps = path.split("/")
    if "[" in comps[0]:
        return "unmarked"
    # a transform decorates the scope it wraps: ``transpose(jvp(gpt0))``
    transposes = [comp.count("transpose(") for comp in comps]
    marks = [i for i, comp in enumerate(comps)
             if _basename(_unwrap(comp)) in _REPLAY_MARKS]
    if marks:
        last = marks[-1]
        transposed = any(transposes[last:]) \
            or any(count > 1 for count in transposes[:last])
        return "backward" if transposed else "replay"
    if any(transposes):
        return "backward"
    if scope != "unscoped" or any("jvp(" in comp for comp in comps):
        return "forward"
    return "unmarked"


def _model_scope_key(path: str) -> str:
    """The main model's fold of a name-stack path (see :func:`scope_key`).

    Keys: ``decode/cache_read|cache_write|sampling``, ``optimizer``,
    ``head_loss``, ``input/embed``, ``input``, ``body/<layer>``,
    ``body/moe/router|dispatch|experts|combine|shared|latent_down|
    latent_up``,
    ``body/moe/router/down|carry|mlp|carried``, ``body/route_early``,
    ``body/attention/gate|q_down|q_norm|
    q_proj|kv_down|kv_norm|kv_up|latent_rope|out_proj|halves|own_block|
    lse_merge``, ``denoise/noise|join|split``,
    ``body/attention/sparse_attention/compress|index|select|attend|
    index_loss``,
    ``body/lightning/in_proj|qk_norm|rope|rule|gate_norm|out_proj``,
    ``body/cca/in_proj|qk_mean|conv|qk_norm|rope|value_shift|out_proj``,
    ``body/merge``,
    ``body/mamba/in_proj|conv|ssd|gate_norm|out_proj``,
    ``body/gated_delta/in_proj|conv|delta_rule|gate_norm|out_proj``,
    ``body/kda/in_proj|conv|decay|rule|gate_norm|out_proj``,
    ``output/unembed``,
    ``output``, ``loss``, ``unscoped``.  Transform decorations
    (``jvp``/``transpose``/``jit`` wrappers) are unwrapped, so forward,
    replay and backward ops of one block fold into the same scope — the
    pass is :func:`pass_key`'s to tell."""
    phase = None
    layer = None
    router = sparse = denoise = False
    bases = []
    for comp in str(path).split("/"):
        base = _basename(_unwrap(comp))
        bases.append(base)
        if base in _SPECIAL:
            return _SPECIAL[base]
        if denoise and base in _DENOISE_PARTS:
            return f"{_DENOISE}/{base}"
        if base == _DENOISE:
            denoise = True
        elif phase is None and base in _PHASES:
            phase = base
        elif phase is not None and layer is None and base in _LAYER_NAMES:
            layer = base
        elif router and base in _ROUTER_PARTS:
            return f"body/moe/router/{base}"
        elif layer == "moe" and base == "router":
            router = True
        elif layer == "moe" and base in _MOE_PARTS and not router:
            return f"body/moe/{base}"
        elif layer == "cca" and base in _CCA_PARTS:
            return f"body/cca/{base}"
        elif sparse and base in _SPARSE_PARTS:
            return f"body/attention/sparse_attention/{base}"
        elif layer == "attention" and base == "sparse_attention":
            sparse = True
        elif layer == "attention" and base in _ATTENTION_PARTS:
            return f"body/attention/{base}"
        elif layer == "lightning" and base in _LIGHTNING_PARTS:
            return f"body/lightning/{base}"
        elif layer == "mamba" and base in _MAMBA_PARTS:
            return f"body/mamba/{base}"
        elif layer == "gated_delta" and base in _DELTA_PARTS:
            return f"body/gated_delta/{base}"
        elif layer == "kda" and base in _KDA_PARTS:
            return f"body/kda/{base}"
    if denoise:
        return _DENOISE
    if router:
        return "body/moe/router"
    if sparse:
        return "body/attention/sparse_attention"
    # a leading block (input_block_config) is a body layer that runs once
    if phase in ("body", "input") and layer is not None:
        return f"body/{layer}"
    if phase == "input":
        return "input/embed" if ("embed" in bases or "gather" in bases) \
            else "input"
    if phase == "output":
        return "output/unembed" if "embed" in bases else "output"
    if phase is not None:
        return phase
    return "unscoped"


# ---- ledger build ----------------------------------------------------------

def _fold_scopes(raw: typing.Mapping[str, typing.Tuple[int, int]]
                 ) -> typing.Dict[str, typing.Dict[str, int]]:
    scopes: typing.Dict[str, typing.Dict[str, int]] = {}
    for stack, (fl, by) in raw.items():
        s = scopes.setdefault(scope_key(stack), {"flops": 0, "bytes": 0})
        s["flops"] += int(fl)
        s["bytes"] += int(by)
    return scopes


def _roofline():
    from ..utils import flops as flops_mod
    return (flops_mod.PEAK_TFLOPS[ROOFLINE_DEVICE],
            flops_mod.HBM_BANDWIDTH[ROOFLINE_DEVICE])


def scope_table(jaxpr, peak: typing.Optional[float] = None,
                bandwidth: typing.Optional[float] = None
                ) -> typing.Dict[str, typing.Any]:
    """``{"total": {...}, "scopes": {scope: {flops, bytes, flops_share,
    bytes_share, intensity, bound}}}`` for ONE traced jaxpr — the core of the
    per-entry ledger.

    ``peak``/``bandwidth`` override the :data:`ROOFLINE_DEVICE` ridge.
    The committed ledger always classifies against the fixed reference
    chip (determinism across rigs); callers describing a CONCRETE device
    run — bench rows — pass the measured device's roofline instead, so a
    scope isn't labelled hbm-bound by a ridge the benchmarked chip doesn't
    have."""
    from ..utils import flops as flops_mod
    scopes = _fold_scopes(flops_mod.scope_costs(jaxpr))
    tot_f = sum(s["flops"] for s in scopes.values())
    tot_b = sum(s["bytes"] for s in scopes.values())
    ref_peak, ref_bw = _roofline()
    peak = ref_peak if peak is None else peak
    bw = ref_bw if bandwidth is None else bandwidth
    for s in scopes.values():
        s["flops_share"] = round(s["flops"] / tot_f, 6) if tot_f else 0.0
        s["bytes_share"] = round(s["bytes"] / tot_b, 6) if tot_b else 0.0
        s["intensity"] = round(s["flops"] / s["bytes"], 4) if s["bytes"] \
            else 0.0
        s["bound"] = flops_mod.roofline_bound(s["flops"], s["bytes"],
                                              peak, bw)
    return {"total": {"flops": tot_f, "bytes": tot_b,
                      "intensity": round(tot_f / tot_b, 4) if tot_b else 0.0,
                      "bound": flops_mod.roofline_bound(tot_f, tot_b,
                                                        peak, bw)},
            "scopes": scopes}


def _xla_costs(compiled) -> typing.Optional[dict]:
    """Whole-module flops / bytes-accessed from XLA's own cost model —
    recorded for cross-checking the analytical counts, NOT regression-
    checked (backend- and version-dependent)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    out = {}
    if ca.get("flops") is not None:
        out["flops"] = float(ca["flops"])
    if ca.get("bytes accessed") is not None:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out or None


def build_ledger(lowered: typing.Optional[dict] = None,
                 overrides: typing.Optional[dict] = None) -> dict:
    """The full ledger dict (the ``cost_ledger.json`` schema) from lowered
    entry points (``entry_points.lower_all``; compiled fresh when None)."""
    from . import entry_points
    if lowered is None:
        lowered = entry_points.lower_all(overrides)
    entries = {}
    for entry in entry_points.ENTRY_POINTS:
        _, ctx = lowered[entry]
        table = scope_table(ctx["trace"]())
        xla = _xla_costs(ctx["compiled"])
        if xla is not None:
            table["xla_cost_analysis"] = xla
        entries[entry] = table
    return {
        "_comment": [
            "Per-entry, per-scope cost ledger at the AUDIT_CONFIG scale",
            "(analysis/entry_points.py).  flops: exact matmul FLOPs from",
            "the traced jaxpr (scans x trip count, full-square convention);",
            "bytes: unfused operand+result traffic (uniform upper bound);",
            "bound: compute- vs hbm- against the roofline_device ridge",
            "point.  graft_lint --hlo regression-checks flops/bytes per",
            "scope against a fresh build within `tolerance` — drift means",
            "the model graph's cost structure changed; if intentional, run",
            "`python -m homebrewnlp_tpu.analysis.cost_ledger --write` and",
            "explain the shift in the PR (docs/STATIC_ANALYSIS.md).",
            "xla_cost_analysis is informational (backend-dependent), never",
            "regression-checked."],
        "roofline_device": ROOFLINE_DEVICE,
        "tolerance": DEFAULT_TOLERANCE,
        "entry_points": entries,
    }


# ---- persistence + regression audit ---------------------------------------

def load_ledger(path: typing.Optional[str] = None) -> typing.Optional[dict]:
    p = path or LEDGER_PATH
    if not os.path.exists(p):
        return None
    with open(p) as f:
        return json.load(f)


def write_ledger(ledger: typing.Optional[dict] = None,
                 path: typing.Optional[str] = None) -> str:
    p = path or LEDGER_PATH
    ledger = ledger if ledger is not None else build_ledger()
    with open(p, "w") as f:
        json.dump(ledger, f, indent=1, sort_keys=True)
        f.write("\n")
    return p


_UPDATE_HINT = ("if the cost structure changed intentionally, run `python "
                "-m homebrewnlp_tpu.analysis.cost_ledger --write` and "
                "explain the shift in the PR (docs/STATIC_ANALYSIS.md)")


def ledger_audit(lowered: typing.Optional[dict] = None,
                 path: typing.Optional[str] = None,
                 current: typing.Optional[dict] = None
                 ) -> typing.List[hlo_lint.Finding]:
    """Regression-check a fresh ledger build against the committed one.

    Tolerance is RELATIVE per scope per metric; a scope appearing or
    vanishing is always a finding (a new model region must be ledgered, a
    vanished one usually means attribution broke).  Zero-total entries are
    compared structurally only."""
    stored = load_ledger(path)
    if stored is None:
        return [hlo_lint.Finding(
            "cost-ledger", "analysis/cost_ledger.json",
            "ledger file missing — every entry point must carry a committed "
            "cost ledger; " + _UPDATE_HINT)]
    if current is None:
        current = build_ledger(lowered)
    tol = float(stored.get("tolerance", DEFAULT_TOLERANCE))
    findings: typing.List[hlo_lint.Finding] = []
    stored_entries = stored.get("entry_points", {})
    for gone in sorted(set(stored_entries) - set(current["entry_points"])):
        findings.append(hlo_lint.Finding(
            "cost-ledger", gone,
            "entry point vanished from the fresh build but is still in the "
            "committed ledger; " + _UPDATE_HINT))
    for entry, cur in current["entry_points"].items():
        if entry not in stored_entries:
            findings.append(hlo_lint.Finding(
                "cost-ledger", entry,
                "entry point missing from the committed ledger; "
                + _UPDATE_HINT))
            continue
        old = stored_entries[entry]
        old_scopes = old.get("scopes", {})
        cur_scopes = cur["scopes"]
        for gone in sorted(set(old_scopes) - set(cur_scopes)):
            findings.append(hlo_lint.Finding(
                "cost-ledger", entry,
                f"scope {gone!r} vanished from the ledger (attribution "
                "broke, or the region was removed); " + _UPDATE_HINT))
        for new in sorted(set(cur_scopes) - set(old_scopes)):
            findings.append(hlo_lint.Finding(
                "cost-ledger", entry,
                f"scope {new!r} is not in the committed ledger; "
                + _UPDATE_HINT))
        for scope in sorted(set(cur_scopes) & set(old_scopes)):
            for metric in ("flops", "bytes"):
                a = float(old_scopes[scope].get(metric, 0))
                b = float(cur_scopes[scope].get(metric, 0))
                base = max(abs(a), 1.0)
                if abs(b - a) / base > tol:
                    findings.append(hlo_lint.Finding(
                        "cost-ledger", entry,
                        f"scope {scope!r} {metric} drifted "
                        f"{a:.3g} -> {b:.3g} (> {tol:.0%} tolerance); "
                        + _UPDATE_HINT))
    return findings


# ---- CLI -------------------------------------------------------------------

def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="build / check the per-scope cost ledger")
    ap.add_argument("--write", action="store_true",
                    help="rebuild analysis/cost_ledger.json from the "
                         "current model (the budget-update protocol)")
    ap.add_argument("--check", action="store_true",
                    help="regression-check against the committed ledger "
                         "(default)")
    ap.add_argument("--path", default=None,
                    help="alternate ledger path (default: "
                         "analysis/cost_ledger.json)")
    args = ap.parse_args(argv)
    if args.write:
        p = write_ledger(path=args.path)
        print(f"cost ledger written to {p}")
        return 0
    findings = ledger_audit(path=args.path)
    for f in findings:
        print(f)
    if findings:
        print(f"cost-ledger: {len(findings)} finding(s)")
        return 1
    print("cost-ledger: clean")
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
