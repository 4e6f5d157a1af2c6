"""Readers of the step's PASSES (PR 70): the device time of the traced
window folded by the pass that runs each instruction — forward, replay (a
forward made again for a backward), backward, optimizer — and the bytes the
memory rule keeps so that less is replayed.

The pass is the PROGRAM's to tell: ``homebrewnlp_tpu.analysis.cost_ledger.
pass_key`` folds an instruction's ``tf_op`` (the ``op_name`` path
``program_readers._tf_ops`` reads off the device plane), as ``scope_key``
folds it into a scope.  What writes the pass into that path: jax's own
``transpose(..)`` wrappers and ``jax.checkpoint``'s ``rematted_computation``,
and the program's scope ``replay`` round the forwards it replays itself
(``core/scope.py replay_vjp``).

A number appears only where it is validated.  The Pallas kernels carry their
direction in their NAME (``flash_fwd_causal``, ``delta_rule_bwd``): a
``*_bwd*`` instruction has to fold to ``backward``, a ``*_fwd*`` one to
``forward`` or ``replay``.  Where the time of those that disagree passes
``DIRECTION_LIMIT`` percent of busy time the three shares read ``None`` with
the reason.

Like ``program_readers``, every function takes the ``Run`` and returns a
number, or ``None`` with a line in ``run.notes`` where there is nothing to
read: a run without a trace, a trace without ``tf_op``, a program without
``pass_key`` (a parent of PR 70).
"""
from __future__ import annotations

import functools
import re
import time
import typing

from . import memory_readers, program_readers, readers

#: percent of busy time on direction-named kernels whose pass disagrees with
#: their name beyond which no share is reported
DIRECTION_LIMIT = 1.0
#: a scope's replay is listed from this share of busy time on, percent
SCOPE_LISTED = 0.5
#: unmarked instructions named in the note, largest first
UNMARKED_LISTED = 8
STASH_BYTES = "hbnlp_remat_stash_bytes"
STASH_LAYERS = "hbnlp_remat_stash_layers"

_DIRECTION = re.compile(r"(?:^|_)(fwd|bwd)(?=_|\.|$)")
_AGREES = {"fwd": ("forward", "replay"), "bwd": ("backward",)}


def _folds():
    """The program's ``analysis.cost_ledger`` (``pass_key``, ``scope_key``,
    ``PASSES``), or ``None`` where it has no ``pass_key``."""
    from homebrewnlp_tpu.analysis import cost_ledger
    return cost_ledger if hasattr(cost_ledger, "pass_key") else None


@functools.lru_cache(maxsize=2)
def _op_passes(path: str) -> typing.Optional[typing.Dict[str, tuple]]:
    """``{instruction short name: (pass, scope)}`` for the instructions that
    carry ``tf_op``; folded once for all the pass metrics of a run."""
    tf_op, folds = program_readers._tf_ops(path), _folds()
    if tf_op is None or folds is None:
        return None
    return {name: (folds.pass_key(op), folds.scope_key(op))
            for name, op in tf_op.items()}


def direction(name: str) -> typing.Optional[str]:
    """``"fwd"`` / ``"bwd"`` where an instruction's short name declares one
    (``flash_bwd_dq_causal.2`` -> ``"bwd"``), else ``None``."""
    found = _DIRECTION.search(name)
    return found.group(1) if found else None


def _steps(run) -> int:
    """Whole runs of the step program inside the traced window."""
    rx = re.compile(run.cell.spec.get("programs", {}).get("step", "$^"))
    return sum(len(runs) for name, runs in run.trace["modules"].items()
               if rx.search(name))


def _fold(run) -> typing.Optional[dict]:
    """The window's device self time by pass, by (pass, scope) and by
    direction-named kernel family, the unmarked instructions and those whose
    pass disagrees with their name; ``None`` with a note where there is
    nothing to fold."""
    if run.trace is None or not run.result.trace_path:
        run.notes.append("passes: the run has no reduced trace")
        return None
    folds = _folds()
    if folds is None:
        run.notes.append("passes: the program has no "
                         "analysis.cost_ledger.pass_key")
        return None
    t0 = time.perf_counter()
    passes = _op_passes(run.result.trace_path)
    if passes is None:
        run.notes.append("passes: the trace's device planes carry no tf_op "
                         "stat")
        return None
    by_pass = dict.fromkeys(folds.PASSES, 0.0)
    by_scope: typing.Dict[tuple, float] = {}
    unmarked: typing.Dict[str, float] = {}
    kernels: typing.Dict[str, typing.Dict[str, list]] = {}
    wrong: typing.Dict[str, float] = {}
    calls = run.trace["calls"]
    for name, seconds in run.trace["ops"].items():
        which, scope = passes.get(name, ("unmarked", "unscoped"))
        by_pass[which] += seconds
        by_scope[which, scope] = by_scope.get((which, scope), 0.0) + seconds
        if which == "unmarked":
            unmarked[name] = seconds
        way = direction(name)
        if way is None:
            continue
        family = re.sub(r"\.\d+$", "", name)
        had = kernels.setdefault(family, {}).setdefault(which, [0, 0.0])
        had[0] += calls.get(name, 0)
        had[1] += seconds
        if which not in _AGREES[way]:
            wrong[f"{name} ({which})"] = seconds
    return {"by_pass": by_pass, "by_scope": by_scope, "kernels": kernels,
            "unmarked": unmarked, "wrong": wrong, "busy": run.trace["busy_s"],
            "fold_s": time.perf_counter() - t0, "instructions": len(passes)}


def _refused(run, fold: dict, listed: bool = False) -> bool:
    """The direction check: True, with the reason in the notes, where the
    time that disagrees passes ``DIRECTION_LIMIT``; ``listed``: name every
    instruction that disagrees."""
    busy, wrong = fold["busy"], fold["wrong"]
    share = 100.0 * sum(wrong.values()) / busy if busy else 0.0
    if wrong and listed:
        run.notes.append(
            f"passes: direction-named kernels whose pass disagrees with "
            f"their name, {share:.4f}% of busy time: " + "; ".join(
                f"{n} {100 * v / busy:.4f}%"
                for n, v in sorted(wrong.items(), key=lambda kv: -kv[1])))
    if share > DIRECTION_LIMIT:
        run.notes.append(
            f"passes: NOT REPORTED — {share:.4f}% of busy time on kernels "
            f"named for one direction folds to the other (limit "
            f"{DIRECTION_LIMIT}%)")
        return True
    return False


def pass_share(run, which: str) -> typing.Optional[float]:
    """Device self time on instructions of pass ``which`` over busy time,
    percent; ``None`` where the direction check refuses the fold."""
    fold = _fold(run)
    if fold is None or not fold["busy"] or _refused(run, fold):
        return None
    return readers.share(fold["by_pass"][which], fold["busy"])


def stash(metric: str) -> typing.Dict[str, float]:
    """``{kind: value}`` of the program's gauge ``metric{kind}``."""
    entry = program_readers.snapshot().get(metric)
    if entry is None:
        return {}
    at = tuple(entry.get("labels", ())).index("kind")
    out: typing.Dict[str, float] = {}
    for key, value in entry["series"].items():
        out[key[at]] = out.get(key[at], 0.0) + float(value)
    return out


def replay_share(run) -> typing.Optional[float]:
    """``pass_share(run, "replay")`` with what an issue writer reads in the
    notes: every pass's share, the replay by scope, the direction-named
    kernel families' calls by pass beside the layers the memory rule keeps,
    the unmarked rest's largest instructions, what sits in fusions, and what
    the fold took."""
    fold = _fold(run)
    if fold is None or not fold["busy"]:
        return None
    busy = fold["busy"]

    def pct(seconds):
        return f"{100 * seconds / busy:.4f}%"

    run.notes.append("pass shares of busy time: " + ", ".join(
        f"{p} {pct(v)}" for p, v in fold["by_pass"].items()))
    listed = sorted(((v, s) for (p, s), v in fold["by_scope"].items()
                     if p == "replay" and 100 * v / busy >= SCOPE_LISTED),
                    reverse=True)
    run.notes.append(
        f"replay by scope (every scope from {SCOPE_LISTED}% of busy time): "
        + (", ".join(f"{s} {pct(v)}" for v, s in listed) or "none"))
    steps = _steps(run)
    for family, by in sorted(fold["kernels"].items()):
        run.notes.append(
            f"kernel {family}: " + " + ".join(
                f"{n} {p} ({pct(s)})" for p, (n, s) in sorted(by.items()))
            + f" calls in the window, {steps} whole steps")
    layers = stash(STASH_LAYERS)
    run.notes.append("hbnlp_remat_stash_layers by kind: " + (", ".join(
        f"{k} {int(v)}" for k, v in sorted(layers.items())) or "no series"))
    labels = run.trace["labels"]
    tf_op = program_readers._tf_ops(run.result.trace_path)
    loose = sorted(((v, n) for n, v in fold["unmarked"].items()),
                   reverse=True)[:UNMARKED_LISTED]
    if loose:
        run.notes.append("largest unmarked instructions: " + "; ".join(
            f"{labels.get(n, n)} {pct(v)} (tf_op {tf_op.get(n, 'absent')!r})"
            for v, n in loose))
    fused = sum(v for n, v in run.trace["ops"].items()
                if re.search(r" k[A-Z]\w*", labels.get(n, "")))
    run.notes.append(
        f"fusions hold {pct(fused)} of busy time: a fusion carries its "
        f"ROOT's tf_op, so what it fused from another pass counts with the "
        f"root's")
    run.notes.append(
        f"pass fold: {fold['instructions']} instructions with tf_op folded "
        f"and {len(run.trace['ops'])} summed in {fold['fold_s'] * 1e3:.3f} "
        f"ms")
    if _refused(run, fold, listed=True):
        return None
    return readers.share(fold["by_pass"]["replay"], busy)


def remat_stash_share(run) -> typing.Optional[float]:
    """The sum over ``kind`` of ``hbnlp_remat_stash_bytes{kind}`` over the
    chip's limit as the program read it at ``step_loaded``, percent."""
    nbytes = stash(STASH_BYTES)
    if not nbytes:
        run.notes.append(f"MISSING: the program's registry holds no "
                         f"{STASH_BYTES}")
        return None
    limit = memory_readers.hbm(run, "step_loaded", "limit")
    if limit is None:
        return None
    layers = stash(STASH_LAYERS)
    run.notes.append(
        "remat stash by kind: " + ", ".join(
            f"{kind} {int(size)} bytes in {int(layers.get(kind, 0))} layers"
            for kind, size in sorted(nbytes.items()))
        + f"; limit {int(limit)} bytes")
    return readers.share(sum(nbytes.values()), limit)
