"""Matmul FLOP counting + MFU.

Model FLOPs are counted exactly by walking a jaxpr and summing
``2 * M * N * K * batch`` over every ``dot_general`` (descending into scans
with their trip counts, pjit/custom-vjp calls, etc.).  MFU follows the
standard convention: useful model FLOPs = 3x the forward pass (forward +
2x backward), NOT the executed FLOPs — rematerialization (revnet/checkpoint
recompute) does not get credit.  The reference had no FLOP accounting at all
(SURVEY.md §5.1: wall-clock phase prints only).
"""
from __future__ import annotations

import re
import typing

import jax
import numpy as np

from ..telemetry import memory

# Per-chip figures by ``device_kind``.  TPU rows are the published chip
# figures (Google Cloud TPU documentation; v5e: 197 TFLOP/s bf16, 16 GB HBM
# at 819 GB/s); int8 peaks are 2x the bf16 ones.  The ``cpu`` rows are
# nominal planning figures so CPU runs (tests, the committed cost ledger)
# still classify — they apply to the CPU platform ONLY: on any other
# platform a kind missing from a table is an error
# (:class:`UnknownDeviceKindError`), never a default, because these numbers
# steer the program (remat auto policy, fused-backward buffer cap).

# bf16 peak FLOP/s (MXU)
PEAK_TFLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5": 459e12,        # v5p
    "TPU v5p": 459e12,
    "TPU v4": 275e12,
    "TPU v4 lite": 138e12,   # v4i inference
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
    "cpu": 1e12,
}

# sustained HBM bandwidth (bytes/s): the other half of the roofline —
# arithmetic intensity above PEAK_TFLOPS/bandwidth is compute-bound, below
# it HBM-bound
HBM_BANDWIDTH = {
    "TPU v5 lite": 819e9,    # v5e
    "TPU v5e": 819e9,
    "TPU v5": 2765e9,        # v5p
    "TPU v5p": 2765e9,
    "TPU v4": 1228e9,
    "TPU v4 lite": 614e9,
    "TPU v6 lite": 1640e9,   # v6e / Trillium
    "TPU v6e": 1640e9,
    "cpu": 50e9,
}

# HBM bytes: what capacity planning (stash auto-enable, fused-backward
# dq-partial cap) uses when the device reports no memory_stats — an AOT
# topology device, or the CPU
HBM_BYTES = {
    "TPU v5 lite": int(15.75 * 1024 ** 3),   # v5e
    "TPU v5e": int(15.75 * 1024 ** 3),
    "TPU v5": 95 * 1024 ** 3,                # v5p
    "TPU v5p": 95 * 1024 ** 3,
    "TPU v4": 32 * 1024 ** 3,
    "TPU v4 lite": 8 * 1024 ** 3,
    "TPU v6 lite": 32 * 1024 ** 3,           # v6e / Trillium
    "TPU v6e": 32 * 1024 ** 3,
    "cpu": 16 * 1024 ** 3,
}


class UnknownDeviceKindError(LookupError):
    """A non-CPU device whose ``device_kind`` has no row in a table above."""


def _kind_lookup(table: typing.Mapping[str, float], device: jax.Device):
    if device.platform == "cpu":
        return table["cpu"]
    kind = device.device_kind
    if kind not in table:
        raise UnknownDeviceKindError(
            f"device kind {kind!r} (platform {device.platform!r}) has no "
            f"row in utils/flops.py — add its published figures; known "
            f"kinds: {sorted(k for k in table if k != 'cpu')}")
    return table[kind]


def hbm_capacity(device: typing.Optional[jax.Device] = None
                 ) -> typing.Tuple[int, str]:
    """``(bytes, source)`` of per-chip memory for capacity planning: the
    runtime's own ``memory_stats()['bytes_limit']`` where the device
    reports one (a live chip), else the ``HBM_BYTES`` row of its kind
    (``source`` says which, e.g. ``"memory_stats"`` / ``"table:TPU v5
    lite"``)."""
    if device is None:
        device = jax.devices()[0]
    stats = memory.device_stats(device)
    if stats and stats.get("limit"):
        return stats["limit"], "memory_stats"
    kind = "cpu" if device.platform == "cpu" else device.device_kind
    return int(_kind_lookup(HBM_BYTES, device)), f"table:{kind}"


def device_hbm_bytes(device: typing.Optional[jax.Device] = None) -> int:
    return hbm_capacity(device)[0]


def describe_devices() -> str:
    """The start-up line every run mode prints: what jax found, and where
    the memory figure that steers capacity planning came from."""
    devices = jax.devices()
    hbm_bytes, source = hbm_capacity(devices[0])
    return (f"devices: platform={devices[0].platform} "
            f"kind={devices[0].device_kind!r} count={len(devices)} "
            f"jax={jax.__version__} hbm_bytes={hbm_bytes} ({source})")


def peak_flops(device: typing.Optional[jax.Device] = None) -> float:
    if device is None:
        device = jax.devices()[0]
    return _kind_lookup(PEAK_TFLOPS, device)


def peak_hbm_bandwidth(device: typing.Optional[jax.Device] = None) -> float:
    """Sustained HBM bytes/s for the device kind (table above) — the decode
    cache-read roofline PR 2 proved governs big-cache serving."""
    if device is None:
        device = jax.devices()[0]
    return _kind_lookup(HBM_BANDWIDTH, device)


def roofline_bound(flops: float, bytes_: float,
                   peak: float, bandwidth: float) -> str:
    """``"compute"`` when the arithmetic intensity (flops/byte) clears the
    ridge point ``peak/bandwidth``, else ``"hbm"`` — the classification the
    cost ledger records per scope (analysis/cost_ledger.py)."""
    if bytes_ <= 0:
        return "compute" if flops > 0 else "hbm"
    return "compute" if flops / bytes_ >= peak / bandwidth else "hbm"


def _dot_flops(eqn) -> int:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    batch = int(np.prod([lhs.shape[i] for i in lb], dtype=np.int64)) if lb else 1
    k = int(np.prod([lhs.shape[i] for i in lc], dtype=np.int64)) if lc else 1
    m = int(np.prod([d for i, d in enumerate(lhs.shape)
                     if i not in set(lc) | set(lb)], dtype=np.int64))
    n = int(np.prod([d for i, d in enumerate(rhs.shape)
                     if i not in set(rc) | set(rb)], dtype=np.int64))
    return 2 * batch * m * n * k


def _conv_flops(eqn) -> int:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    out = eqn.outvars[0].aval
    # 2 * output elements * kernel-window size * input feature depth
    dn = eqn.params["dimension_numbers"]
    kshape = rhs.shape
    spatial_k = int(np.prod([kshape[i] for i in dn.rhs_spec[2:]], dtype=np.int64))
    cin = kshape[dn.rhs_spec[1]]
    return 2 * int(np.prod(out.shape, dtype=np.int64)) * spatial_k * cin


def count_matmul_flops(jaxpr) -> int:
    """Total dot/conv FLOPs in a (closed) jaxpr, scans scaled by length.

    Full-square convention: every pallas grid cell is counted as if live,
    including the causally-dead cells the flash kernels skip.  Kept stable
    round-over-round; use :func:`count_matmul_flops_split` for the
    executed-FLOP (causal) count alongside it."""
    return count_matmul_flops_split(jaxpr)[0]


def count_matmul_flops_split(jaxpr) -> typing.Tuple[int, int]:
    """(full, executed) dot/conv FLOPs of a (closed) jaxpr.

    ``full`` is the stable full-square convention (see
    :func:`count_matmul_flops`).  ``executed`` subtracts the causally-dead
    grid cells of causal pallas kernels (the cells ``pl.when`` skips —
    flash_attention.py names those calls ``*_causal``), i.e. the FLOPs the
    hardware actually performs.  Dense masked attention (the XLA fallback)
    executes the full square, so there ``executed == full``."""
    total, dead = _count_split(jaxpr)
    return total, total - dead


def _descend(eqn):
    """``(inner_jaxpr, trip_multiplier)`` of a higher-order equation, or
    None for leaves.  The ONE primitive/param-key table both jaxpr walkers
    (:func:`_count_split` and :func:`_scope_walk`) descend through — a jax
    upgrade renaming a param key gets fixed here once, instead of letting
    the MFU count and the cost ledger silently disagree.  ``cond`` and
    ``pallas_call`` are excluded: their conventions differ per walker
    (max-branch vs dead-cell accounting) but share :func:`_pallas_grid`."""
    prim = eqn.primitive.name
    if prim == "scan":
        return eqn.params["jaxpr"].jaxpr, int(eqn.params["length"])
    if prim == "while":
        # trip count unknown; count one body iteration
        return eqn.params["body_jaxpr"].jaxpr, 1
    if prim in ("custom_vjp_call", "custom_jvp_call",
                "custom_vjp_call_jaxpr", "remat", "checkpoint"):
        inner = eqn.params.get("call_jaxpr") or eqn.params.get("fun_jaxpr")
    elif prim in ("pjit", "jit", "xla_call", "closed_call", "core_call",
                  "shard_map"):
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
    else:
        return None
    if inner is None:
        return None
    return getattr(inner, "jaxpr", inner), 1


def _pallas_grid(eqn):
    """``(inner_jaxpr_or_None, grid, cells)`` of a ``pallas_call`` — the
    kernel body runs once per grid cell, so FLOPs are grid product × body
    FLOPs.  The flash one-pass backward's grid (batch·heads, steps) walks
    only the LIVE cells of its ``q blocks x k blocks`` rectangle, by
    prefetched tables (parallel/flash_attention.py ``_live_steps``): it is
    counted as that rectangle — the full-square convention — read off its q
    and k operands' blocks."""
    inner = eqn.params.get("jaxpr")
    gm = eqn.params.get("grid_mapping")
    grid = getattr(gm, "grid", ()) if gm is not None else ()
    if len(grid) == 2 and gm.num_index_operands \
            and str(eqn.params.get("name", "")).startswith("flash_bwd_fused"):
        grid = (grid[0],) + tuple(
            m.array_aval.shape[-2] // m.block_shape[-2].block_size
            for m in gm.block_mappings[:2])
    cells = int(np.prod([g for g in grid if isinstance(g, int)],
                        dtype=np.int64)) if grid else 1
    return (getattr(inner, "jaxpr", inner) if inner is not None else None,
            grid, cells)


def _count_split(jaxpr) -> typing.Tuple[int, int]:
    """Recursive core: (full-square total, causally-dead) FLOPs."""
    total = 0
    dead = 0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        inner = _descend(eqn)
        if inner is not None:
            t, d = _count_split(inner[0])
            total += inner[1] * t
            dead += inner[1] * d
        elif prim == "dot_general":
            total += _dot_flops(eqn)
        elif prim == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif prim == "cond":
            branches = eqn.params.get("branches", ())
            if branches:
                t, d = max((_count_split(b.jaxpr)
                            for b in branches), key=lambda td: td[0])
                total += t
                dead += d
        elif prim == "pallas_call":
            # every grid cell counted as if live in ``total`` — the
            # full-square convention for causal flash kernels, kept stable
            # round-over-round.  Causal kernels (name carries "causal";
            # grid (batch·heads, a, b) with {a, b} = {q blocks, k blocks}
            # in either order, or (heads, a, b, batch): every dimension
            # but a and b multiplies) additionally report their skipped
            # cells in ``dead``: live block pairs are the ones overlapping
            # the lower triangle, sum_j min(b, ceil(j·b/a)) —
            # transpose-symmetric, so the (i, q, k) and (i, k, q) grids
            # count identically.  The flash kernels score only the live
            # PART of a cell the diagonal crosses (``_flash_scored``)
            body_jaxpr, grid, cells = _pallas_grid(eqn)
            if body_jaxpr is not None:
                name = str(eqn.params.get("name", ""))
                masked = _FLASH_MASKED.match(name) is not None
                body = _pallas_body_flops(body_jaxpr, exclusive=masked)
                total += cells * body
                if "causal" in name and len(grid) >= 3 \
                        and all(isinstance(g, int) for g in grid):
                    a, b = grid[1], grid[2]
                    scored = _flash_scored(eqn, name, a, b) if masked \
                        else None
                    if scored is None:
                        live = sum(min(b, (j * b + a - 1) // a)
                                   for j in range(1, a + 1))
                        dead += cells // (a * b) * (a * b - live) * body
                    else:
                        pairs, tile = scored
                        dead += cells // (a * b) * (body // tile) \
                            * (a * b * tile - pairs)
    return total, dead


#: the kernels of parallel/flash_attention.py ``_masked_step``: the interior
#: branch and one branch an edge offset, all mutually exclusive
_FLASH_MASKED = re.compile(r"flash_(fwd|bwd_fused|bwd_dq|bwd_dkv)_(causal|window)$")


def _flash_scored(eqn, name: str, a: int, b: int
                  ) -> typing.Optional[typing.Tuple[int, int]]:
    """``(pairs scored a head-sequence, pairs of one tile)`` of a causal
    flash call on a grid of ``a`` x ``b`` cells, from the geometry the
    kernels themselves branch on (``scored_pairs``: an interior cell whole,
    an edge cell its live part) — a cell's FLOPs are its interior branch's
    by the share of the tile it scores, every dot being rows x keys x width.
    None for a call on unequal lengths, which keeps the count by cells."""
    from ..parallel.flash_attention import scored_pairs
    # q and k follow the prefetched step tables, where a call has them
    first = getattr(eqn.params.get("grid_mapping"), "num_index_operands", 0)
    sq, sk = (v.aval.shape[-2] for v in eqn.invars[first:first + 2])
    # grid (b*h, q blocks, k blocks), but for the dk/dv kernel's k-outer one
    nq, nk = (b, a) if "bwd_dkv" in name else (a, b)
    if sq != sk or sq % nq or sk % nk:
        return None
    bq, bk = sq // nq, sk // nk
    return scored_pairs(sq, bq, bk, carried="fwd" in name), bq * bk


def _pallas_body_flops(jaxpr, exclusive: bool = False) -> int:
    """Per-cell FLOPs of a pallas kernel body (``exclusive``: a kernel of
    the ``_masked_step`` family, whose gated branches are ONE cell's
    alternatives — the interior, and an edge branch for each offset that
    scores a part of the same dots — so the cell counts as its largest).

    ``pl.when`` branches lower to ``cond`` eqns; kernels that split the
    causal mask into interior/diagonal variants (parallel/flash_attention.py
    ``_masked_step``) emit MUTUALLY EXCLUSIVE conds containing the SAME
    dots, so summing every cond (as the generic walker does) double-counts.
    Exclusivity is not visible in the jaxpr, but the exclusive mask pair
    always has IDENTICAL per-branch dot counts (same shapes, masked vs
    not) — so equal nonzero cond counts are deduplicated to one, while
    conds with DIFFERING dot counts (two genuinely sequential gated
    stages) are summed; a future two-stage kernel is over- rather than
    silently under-counted."""
    uncond = count_matmul_flops(
        _StrippedJaxpr([e for e in jaxpr.eqns if e.primitive.name != "cond"]))
    conds = [count_matmul_flops(b.jaxpr)
             for e in jaxpr.eqns if e.primitive.name == "cond"
             for b in e.params.get("branches", ())]
    if exclusive:
        return uncond + max(conds, default=0)
    return uncond + sum(set(c for c in conds if c))


class _StrippedJaxpr:
    def __init__(self, eqns):
        self.eqns = eqns


def forward_flops(fn, *args) -> int:
    """Matmul FLOPs of one forward call (traced abstractly, no execution)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return count_matmul_flops(jaxpr.jaxpr)


def forward_flops_split(fn, *args) -> typing.Tuple[int, int]:
    """(full-square, executed) matmul FLOPs of one forward call — the
    executed count excludes the causally-dead cells the flash kernels skip
    (:func:`count_matmul_flops_split`)."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return count_matmul_flops_split(jaxpr.jaxpr)


def mfu(fwd_flops_per_step: float, step_time_s: float, n_chips: int = 1,
        device: typing.Optional[jax.Device] = None) -> float:
    """Model FLOPs utilization: 3x forward FLOPs over peak (no remat credit)."""
    return 3.0 * fwd_flops_per_step / step_time_s / (peak_flops(device) * n_chips)


# ---- per-scope cost attribution (docs/OBSERVABILITY.md) ---------------------
#
# The model graph carries jax.named_scope regions (core/scope.py name_scope
# mirrors every scope frame), so each jaxpr equation's
# ``source_info.name_stack`` names the block/layer that produced it.  The
# walker below attributes {matmul flops, unfused bytes} to those stacks —
# the analytical half of the cost ledger (analysis/cost_ledger.py), which
# folds stacks into coarse scope keys and joins them with XLA's
# cost_analysis and profiler time shares.


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "shape"):
        return 0
    try:
        return int(np.prod(aval.shape, dtype=np.int64)
                   ) * np.dtype(aval.dtype).itemsize
    except TypeError:
        return 0


def _eqn_bytes(eqn) -> int:
    """Operand + result bytes of one equation — the UNFUSED memory-traffic
    convention (fusion elides intermediates on real hardware, so per-scope
    byte totals are an upper bound; shares between scopes stay meaningful
    because the convention is uniform)."""
    return (sum(_aval_bytes(v) for v in eqn.invars)
            + sum(_aval_bytes(v) for v in eqn.outvars))


def scope_costs(jaxpr, prefix: str = ""
                ) -> typing.Dict[str, typing.Tuple[int, int]]:
    """``{name_stack: (flops, bytes)}`` over a (closed) jaxpr.

    Scan bodies multiply by trip count (the full-square convention of
    :func:`count_matmul_flops`); inner jaxprs' stacks are prefixed with the
    enclosing equation's stack, since a sub-trace's name_stack restarts at
    its own trace boundary."""
    out: typing.Dict[str, typing.List[int]] = {}
    _scope_walk(getattr(jaxpr, "jaxpr", jaxpr), prefix, 1, out)
    return {k: (v[0], v[1]) for k, v in out.items()}


def _join_stack(prefix: str, stack: str) -> str:
    if prefix and stack:
        return f"{prefix}/{stack}"
    return prefix or stack


def _scope_walk(jaxpr, prefix: str, mult: int, out) -> None:
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        path = _join_stack(prefix, str(eqn.source_info.name_stack))
        inner = _descend(eqn)
        if inner is not None:
            _scope_walk(inner[0], path, mult * inner[1], out)
            continue
        if prim == "cond":
            branches = eqn.params.get("branches", ())
            if branches:
                # the max-flops branch, matching count_matmul_flops
                best = max(branches,
                           key=lambda b: count_matmul_flops(b.jaxpr))
                _scope_walk(best.jaxpr, path, mult, out)
                continue
        flops = 0
        if prim == "dot_general":
            flops = _dot_flops(eqn)
        elif prim == "conv_general_dilated":
            flops = _conv_flops(eqn)
        elif prim == "pallas_call":
            body_jaxpr, _grid, cells = _pallas_grid(eqn)
            if body_jaxpr is not None:
                flops = cells * _pallas_body_flops(
                    body_jaxpr, exclusive=_FLASH_MASKED.match(
                        str(eqn.params.get("name", ""))) is not None)
        ent = out.setdefault(path, [0, 0])
        ent[0] += mult * flops
        ent[1] += mult * _eqn_bytes(eqn)
