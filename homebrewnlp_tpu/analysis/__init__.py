"""graft-lint: compiled-artifact + AST static-analysis layer.

The properties that keep a TPU program fast — donation actually aliasing,
no full-buffer copies, no stray collectives from accidental resharding —
live in the COMPILED module, not the traced one, and regress silently
(BASELINE.md round 5: the fused decode loop traced identically at 0.5 GB
and 6.5 GB yet only aliased at the former).  This package audits them
mechanically for every jitted entry point instead of one-off per PR:

- ``hlo_lint``   — parameterized passes over compiled-HLO text (donation
  audit, big-copy detection, dtype-promotion audit, collective census vs
  ``budgets.json``, host-sync detection).  Stdlib-only at import; jax is
  needed only to produce the HLO you feed it.
- ``ast_lint``   — repo-specific source rules (wall-clock discipline,
  unseeded rngs, donated-jit registration, config-docs coverage).
  Stdlib-only and importable standalone (by file path, without the
  package).
- ``entry_points`` — builds a small audit model on the current backend and
  lowers the registered jitted entry points (train step, decode chunk
  step, prefill-entry step, eval fn, engine chunk step) for the HLO
  passes.
- ``mesh_audit``  — lowers the entry points under every parallel strategy
  (dp x tp, ring SP, MoE EP, the pipeline schedules) on 8 virtual CPU
  devices and audits per-mesh collective budgets, sharding contracts,
  and peak-HBM liveness against the ``meshes`` section of
  ``budgets.json``.
- ``cost_ledger`` — per-entry, per-scope analytical flops/bytes ledger
  regression-checked against ``cost_ledger.json``.

Run everything: ``python scripts/graft_lint.py --all`` (docs/STATIC_ANALYSIS.md).
"""
from . import ast_lint, hlo_lint  # noqa: F401

__all__ = ["ast_lint", "hlo_lint", "entry_points", "mesh_audit",
           "cost_ledger"]


def __getattr__(name):
    # entry_points imports model/train/infer machinery (and, inside its
    # functions, jax); load it lazily so `import homebrewnlp_tpu.analysis`
    # stays cheap for AST-only consumers
    if name in ("entry_points", "mesh_audit", "cost_ledger"):
        import importlib
        return importlib.import_module("." + name, __name__)
    raise AttributeError(name)
