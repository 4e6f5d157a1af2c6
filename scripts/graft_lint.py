#!/usr/bin/env python3
"""graft-lint: run the repo's static-analysis layer from one entry point.

Three pass families (docs/STATIC_ANALYSIS.md):

  --ast   AST rules over ``homebrewnlp_tpu/`` and ``scripts/`` (wall-clock
          discipline, unseeded rngs, donated-jit registration, mesh-axis
          literals, config-docs coverage).  Stdlib-only, runs in well
          under a second.
  --hlo   compiled-HLO audit of every registered jitted entry point (train
          step, decode chunk step, prefill entry, eval fn, engine chunk
          step): donation, big-copy, dtype-promotion, collective census vs
          ``analysis/budgets.json``, host-sync, cost-ledger regression.
          Compiles a small audit model on the current backend (~15 s CPU).
  --conc  host-concurrency audit (analysis/conc_lint.py): lock-discipline
          AST lint over the serving/elastic control plane (GUARDED_BY
          registry, blocking-call-under-lock, lock-ordering cycles,
          thread hygiene) plus the deterministic interleaving explorer
          (analysis/interleave.py) replaying the control-plane scenarios
          under permuted schedules.  With ``HBNLP_LOCK_TRACE=<dir>``
          pointing at a recorded run, the observed acquisition-order
          edges join the same cycle check.
  --mesh  mesh-aware audit (analysis/mesh_audit.py): the registered entry
          points lowered under every pod_lowering strategy (dp x tp, ring
          SP, MoE EP, the pipeline schedules) on 8 virtual CPU devices —
          per-mesh collective budgets (surplus collectives named WITH the
          mesh axis they reshard over), sharding-spec contracts, peak-HBM
          liveness.  When the current process has fewer than 8 devices the
          mesh half re-runs itself in a CPU-virtual subprocess (the dryrun
          bootstrap idiom), so the single-device --hlo audit keeps the
          current backend.
  --all   everything (the pre-push / CI mode; also the default with no
          flags).  The single-device entry points are lowered ONCE and
          shared between the HLO audits and the cost-ledger check; the
          mesh half lowers only its sharded variants.

Exit status is the number of findings clamped to 1 — nonzero means the
repo violates an invariant.  The summary groups findings per rule so CI
logs show at a glance WHICH invariant broke.
"""
from __future__ import annotations

import argparse
import collections
import os
import re
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def run_ast() -> list:
    from homebrewnlp_tpu.analysis import ast_lint
    return ast_lint.lint_repo()


def run_conc() -> list:
    # the blockpool scenario imports infer/paged -> engine -> jax; pin the
    # platform so --conc never grabs a TPU from a CI box that has one
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from homebrewnlp_tpu.analysis import conc_lint
    edges = set()
    findings = conc_lint.explorer_findings(edges=edges)
    findings += conc_lint.lint_repo_conc(extra_edges=edges)
    return findings


def run_hlo(budgets_path=None, ledger_path=None) -> list:
    from homebrewnlp_tpu.analysis import cost_ledger, entry_points, hlo_lint
    budgets = hlo_lint.load_budgets(budgets_path) if budgets_path else None
    # one lower_all feeds BOTH the HLO audits and the cost-ledger
    # regression check — the five entry-point compiles are the cost here,
    # shared so --all stays within its CPU time budget
    lowered = entry_points.lower_all()
    findings = entry_points.audit_lowered(lowered, budgets=budgets)
    findings += cost_ledger.ledger_audit(lowered, path=ledger_path)
    return findings


_FINDING_LINE = re.compile(r"^\[([\w-]+)\] ([^:]+): (.*)$")


def run_mesh(budgets_path=None) -> list:
    """Mesh passes in-process when the process already exposes 8 CPU
    devices (the test rig), else in a CPU-virtual subprocess so the
    --hlo half keeps auditing the CURRENT backend.  The committed meshes
    budgets are CPU-virtual lowerings by definition — auditing them
    against a TPU backend's compile would flag honest backend drift as
    findings, so a non-CPU process always takes the subprocess."""
    import jax

    from homebrewnlp_tpu.analysis import hlo_lint, mesh_audit

    if budgets_path:
        budgets_path = os.path.abspath(budgets_path)
    if (jax.default_backend() == "cpu"
            and len(jax.devices()) >= mesh_audit.MESH_DEVICES):
        budgets = (hlo_lint.load_budgets(budgets_path)
                   if budgets_path else None)
        findings, skipped = mesh_audit.audit_meshes(budgets)
        for name, reason in sorted(skipped.items()):
            print(f"mesh-audit: strategy {name!r} SKIPPED — environment "
                  f"gap: {reason}")
        return findings

    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   os.environ.get("XLA_FLAGS", ""))
    flags += (" --xla_force_host_platform_device_count="
              f"{mesh_audit.MESH_DEVICES}")
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=flags)
    cmd = [sys.executable, "-m", "homebrewnlp_tpu.analysis.mesh_audit",
           "--check"]
    if budgets_path:
        cmd += ["--budgets", budgets_path]
    proc = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                          text=True)
    findings = []
    for line in proc.stdout.splitlines():
        m = _FINDING_LINE.match(line)
        if m is not None:
            findings.append(hlo_lint.Finding(m.group(1), m.group(2),
                                             m.group(3)))
        elif line.startswith("mesh-audit: strategy"):
            print(line)
    if proc.returncode != 0 and not findings:
        findings.append(hlo_lint.Finding(
            "mesh-audit", "subprocess",
            f"mesh audit subprocess failed (rc={proc.returncode}):\n"
            + (proc.stderr or proc.stdout)[-2000:]))
    return findings


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ast", action="store_true",
                    help="AST rules only (fast, no jax)")
    ap.add_argument("--conc", action="store_true",
                    help="host-concurrency audit only (lock lint + "
                         "interleaving explorer)")
    ap.add_argument("--hlo", action="store_true",
                    help="compiled-HLO entry-point audit only")
    ap.add_argument("--mesh", action="store_true",
                    help="mesh-aware strategy audit only (8 virtual CPU "
                         "devices)")
    ap.add_argument("--all", action="store_true",
                    help="every pass family (default when no flags given)")
    ap.add_argument("--budgets", default=None,
                    help="alternate budgets.json (default: "
                         "analysis/budgets.json)")
    ap.add_argument("--ledger", default=None,
                    help="alternate cost_ledger.json (default: "
                         "analysis/cost_ledger.json)")
    args = ap.parse_args(argv)
    none_picked = not (args.ast or args.conc or args.hlo or args.mesh)
    do_ast = args.ast or args.all or none_picked
    do_conc = args.conc or args.all or none_picked
    do_hlo = args.hlo or args.all or none_picked
    do_mesh = args.mesh or args.all or none_picked

    findings = []
    t0 = time.monotonic()
    if do_ast:
        findings += run_ast()
    if do_conc:
        findings += run_conc()
    if do_hlo:
        findings += run_hlo(args.budgets, args.ledger)
    if do_mesh:
        findings += run_mesh(args.budgets)
    dt = time.monotonic() - t0

    for f in findings:
        print(f)
    per_rule = collections.Counter(f.rule for f in findings)
    halves = "+".join(h for h, on in (("ast", do_ast), ("conc", do_conc),
                                      ("hlo", do_hlo),
                                      ("mesh", do_mesh)) if on)
    if findings:
        summary = ", ".join(f"{rule}: {n}" for rule, n
                            in sorted(per_rule.items()))
        print(f"graft-lint [{halves}]: {len(findings)} finding(s) in "
              f"{dt:.1f}s — {summary}", file=sys.stderr)
        return 1
    print(f"graft-lint [{halves}]: clean in {dt:.1f}s")
    return 0


if __name__ == "__main__":
    sys.exit(main())
