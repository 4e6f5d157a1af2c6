"""Compiled-HLO audit passes (graft-lint half a).

Every pass takes post-optimization HLO text (``jitted.lower(...).compile()
.as_text()``) plus the caller's expectations and returns ``Finding``s —
nothing raises, so one run can report every violation at once (the CLI and
the tier-1 test decide severity):

=====================  ====================================================
pass                   invariant
=====================  ====================================================
donation_audit         every donated leaf appears in ``input_output_alias``
                       — a dropped or unaliasable donation is a silent 2x
                       HBM regression
big_copy_audit         no ``copy``/``copy-done`` produces a buffer shaped
                       like a caller-supplied protected shape (KV caches
                       for decode, param/opt-state leaves for train)
dtype_promotion_audit  no f32 intermediate ``convert``-ed from a bf16
                       buffer shaped like a bf16 param outside an allowlist
                       (an accidental master-weight copy per step)
collective_budget_audit  collective census (all-reduce/all-gather/
                       reduce-scatter/collective-permute/all-to-all) stays
                       within per-entry-point budgets (``budgets.json``) —
                       catches accidental resharding the way the decode
                       scaling test caught cache copies
host_sync_audit        no host callbacks / infeed / outfeed / send / recv
                       on hot paths
=====================  ====================================================

Import is stdlib+numpy only; jax appears nowhere (callers hand us text).
"""
from __future__ import annotations

import dataclasses
import json
import os
import re
import typing

import numpy as np

BUDGETS_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "budgets.json")


@dataclasses.dataclass(frozen=True)
class Finding:
    """One violation: ``rule`` (pass name), ``entry`` (audited entry point
    or source location), human-readable ``message``."""
    rule: str
    entry: str
    message: str

    def __str__(self):
        return f"[{self.rule}] {self.entry}: {self.message}"


# instruction line: "%name = <shape> <op>(...)" — the op name directly
# follows the result shape (post-layout HLO text).  Async pairs: a
# ``copy-start`` result is a TUPLE shape (unmatchable here), but its
# ``copy-done`` twin's result is the plain copied array shape, so matching
# copy-done catches every async copy exactly once.  The same start/done
# convention holds for collectives below.
_COPY_RE = re.compile(
    r"=\s*([a-z0-9]+\[[0-9,]*\])(\{[^}]*\})?\s+copy\("
    r"\s*(?:[a-z0-9]+\[[0-9,]*\])?(\{[^}]*\})?\s*%([a-zA-Z0-9_.-]+)")

# ``copy-done``'s operand is the copy-start TUPLE ``(dest, src, context)``
# — the tuple's first two member layouts are the copy's out/in layouts
_COPY_DONE_RE = re.compile(
    r"=\s*([a-z0-9]+\[[0-9,]*\])(\{[^}]*\})?\s+copy-done\(\s*\(\s*"
    r"[a-z0-9]+\[[0-9,]*\](\{[^}]*\})?\s*,\s*"
    r"[a-z0-9]+\[[0-9,]*\](\{[^}]*\})?[^%]*%([a-zA-Z0-9_.-]+)")

_CONVERT_RE = re.compile(
    r"=\s*f32\[([0-9,]*)\](?:\{[^}]*\})?\s+convert\(\s*bf16\[([0-9,]*)\]")

#: census ops; ``<op>-start`` is counted and ``<op>-done`` ignored so an
#: async pair counts once (a sync ``<op>`` instruction also counts once)
COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

_COLLECTIVE_RE = re.compile(
    r"=\s*(?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?|\()[^=]*?\s"
    r"(" + "|".join(COLLECTIVE_OPS) + r")(-start)?\(")

_HOST_OP_RE = re.compile(
    r"=\s*(?:[a-z0-9]+\[[^\]]*\](?:\{[^}]*\})?|\()[^=]*?\s"
    r"(infeed|outfeed|send|recv)(-done)?\(")

#: custom-call targets that round-trip through the host (python callbacks,
#: host transfers) — a per-step host sync on a hot path serializes the
#: device against the GIL
_HOST_CALLBACK_RE = re.compile(
    r'custom-call[^\n]*custom_call_target="([^"]*'
    r'(?:callback|host|py_func|infeed|outfeed)[^"]*)"', re.I)


def input_output_alias_count(hlo_text: str) -> int:
    """Number of entries in the entry module's input_output_alias table."""
    start = hlo_text.find("input_output_alias={")
    if start < 0:
        return 0
    # brace-scan to the table's closing brace (entries nest one level:
    # "{0}: (31, {}, may-alias)")
    i = hlo_text.index("{", start)
    depth, end = 0, i
    for end in range(i, len(hlo_text)):
        depth += (hlo_text[end] == "{") - (hlo_text[end] == "}")
        if depth == 0:
            break
    return len(re.findall(r"(?:may|must)-alias", hlo_text[i:end + 1]))


_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16", "float16": "f16",
              "float64": "f64", "int8": "s8", "uint8": "u8", "int16": "s16",
              "int32": "s32", "int64": "s64", "uint32": "u32",
              "uint64": "u64", "bool": "pred"}


def shape_strings(avals: typing.Mapping[str, typing.Any],
                  key_filter: typing.Optional[str] = None,
                  min_rank: int = 0,
                  dtypes: typing.Optional[typing.Container[str]] = None
                  ) -> typing.Set[str]:
    """HLO shape strings (``f32[2,4,16,2,16]``) of a dict of array-likes
    (anything with ``.shape``/``.dtype``).  ``key_filter`` keeps only names
    containing the substring; ``min_rank`` drops small vectors (norm
    scales) when only matrix-shaped buffers matter; ``dtypes`` restricts to
    the given HLO dtype strings (e.g. ``{"bf16"}``)."""
    out = set()
    for name, v in avals.items():
        if key_filter is not None and key_filter not in name:
            continue
        if len(v.shape) < min_rank:
            continue
        dt = _HLO_DTYPE.get(str(np.dtype(v.dtype)))
        if dt is None or (dtypes is not None and dt not in dtypes):
            continue
        out.add(f"{dt}[{','.join(str(d) for d in v.shape)}]")
    return out


# ---- passes ----------------------------------------------------------------

def donation_audit(entry: str, hlo_text: str, expected_aliases: int
                   ) -> typing.List[Finding]:
    """Donation actually took: at least ``expected_aliases`` entries in the
    input_output_alias table.  Callers pass the donated LEAF count — every
    leaf must alias, a count any cache leaf could miss only by another,
    nonexistent leaf standing in for it."""
    got = input_output_alias_count(hlo_text)
    if got < expected_aliases:
        return [Finding("donation", entry,
                        f"only {got} input_output_alias entries (expected "
                        f">= {expected_aliases}): donated buffers are NOT "
                        "aliased in place — each un-aliased donation is a "
                        "full extra copy of that buffer per call")]
    return []


_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "f64": 8, "s8": 1, "u8": 1,
                "s16": 2, "s32": 4, "s64": 8, "u32": 4, "u64": 8, "pred": 1}


def shape_bytes(shape_string: str) -> int:
    """``"f32[2,16]"`` -> 128."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", shape_string)
    if m is None:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(m.group(1), 1)


def big_copy_audit(entry: str, hlo_text: str,
                   protected: typing.Set[str],
                   max_copied_bytes: int = 0,
                   max_offenders: int = 8) -> typing.List[Finding]:
    """No ``copy``/``copy-done`` whose result is exactly a protected shape
    (the aliaser inserts such copies when it cannot prove in-place safety).
    Async pairs count once: ``copy-start``'s tuple result is unmatchable,
    its ``copy-done`` twin carries the copied array shape — at production
    scale XLA emits exactly the big copies this pass polices as async
    pairs, so missing them would blind the audit where it matters most.

    Three copy flavors are legitimate and skipped: differently-shaped
    buffers (row-sized scatter traffic, block-sized slices), copies of a
    fresh ``broadcast``/``constant``/``iota`` result (materializing an
    init value into a loop carry — one write that has to happen anyway,
    not a duplication of live state), and RELAYOUT copies of an explicit
    data-movement result (``transpose``/``bitcast``/``reshape`` operand —
    layout assignment materializing an intermediate the math asked for;
    the train step's optimizer transposes land here).  A relayout copy of
    LIVE state (``get-tuple-element``/parameter operand) is NOT exempt:
    an unaliasable cache layout reintroduces the per-token multi-GB copy
    (the pre-refactor decode checker named it a failure), so it counts
    toward the byte budget like any other full-buffer copy.

    ``max_copied_bytes``: tolerated total bytes of such copies.  0 (the
    decode default) flags ANY protected copy; the train step runs with a
    small fraction of its donated bytes (budgets.json
    ``copy_byte_fraction``) because XLA legitimately preserves a
    multiply-consumed small leaf (e.g. an embedding table read by forward
    AND subtracted by the update) — the failure mode is the dominant
    leaves copying, which blows any small fraction immediately."""
    if not protected:
        return []
    offenders, copied = [], 0
    for line in hlo_text.splitlines():
        m = _COPY_RE.search(line)
        if m is not None:
            shape, out_layout, in_layout, operand = m.groups()
        else:
            m = _COPY_DONE_RE.search(line)
            if m is None:
                continue
            shape, _, out_layout, in_layout, operand = m.groups()
        if shape not in protected:
            continue
        op_kind = operand.split(".")[0]
        if op_kind in ("broadcast", "constant", "iota"):
            continue  # fresh init value, not duplicated live state
        if (out_layout and in_layout and out_layout != in_layout
                and op_kind in ("transpose", "bitcast", "reshape")):
            continue  # layout assignment materializing an intermediate
        copied += shape_bytes(shape)
        offenders.append(line.strip())
    if offenders and copied > max_copied_bytes:
        return [Finding("big-copy", entry,
                        f"{len(offenders)} full-buffer copy(s) of protected "
                        f"shapes ({copied} bytes copied, budget "
                        f"{max_copied_bytes}) — the update is NOT aliased "
                        "in place:\n"
                        + "\n".join(offenders[:max_offenders]))]
    return []


def dtype_promotion_audit(entry: str, hlo_text: str,
                          bf16_param_shapes: typing.Set[str],
                          allow: typing.Collection[str] = ()
                          ) -> typing.List[Finding]:
    """No ``f32[dims] convert(bf16[dims])`` where ``dims`` matches a bf16
    param shape outside ``allow`` — a param-shaped f32 intermediate is an
    accidental master-weight copy materialized every step.  Shapes are
    dims-only strings (``"512,512"``); pass param leaves through
    ``shape_strings(..., dtypes={"bf16"})`` and strip the dtype prefix with
    ``dims_of``."""
    if not bf16_param_shapes:
        return []
    dims_set = {dims_of(s) for s in bf16_param_shapes}
    allow_set = {dims_of(s) for s in allow}
    offenders = []
    for line in hlo_text.splitlines():
        m = _CONVERT_RE.search(line)
        if m is None:
            continue
        out_dims, in_dims = m.group(1), m.group(2)
        if (out_dims == in_dims and out_dims in dims_set
                and out_dims not in allow_set):
            offenders.append(line.strip())
    if offenders:
        return [Finding("dtype-promotion", entry,
                        f"{len(offenders)} f32 intermediate(s) converted "
                        "from bf16-param-shaped buffers (accidental "
                        "master-weight promotion):\n"
                        + "\n".join(offenders[:8]))]
    return []


_INT8_CONVERT_RE = re.compile(
    r"=\s*(?:f32|bf16|f16)\[[0-9,]*\](?:\{[^}]*\})?\s+convert\(\s*"
    r"s8\[[0-9,]*\]")


def int8_promotion_audit(entry: str, hlo_text: str,
                         scopes: typing.Collection[str] = ("dequant",
                                                           "cache_read")
                         ) -> typing.List[Finding]:
    """Every float ``convert`` of an int8 operand must belong to a named
    dequant scope.

    The quantized paths promise int8 reaches float exactly once, inside a
    named fused-dequant region: weights (``serve_quantized_weights``) under
    ``named_scope("dequant")`` (``core.scope.materialize_param``), and int8
    KV caches (``decode_cache_dtype: "int8"``) under the decode
    path's ``named_scope("cache_read")`` (model/decode.py) — both are
    allowed by default.  Any OTHER s8 -> float convert is an accidental
    full-precision materialization of a quantized buffer: it silently
    costs the float copy's HBM and hides the bandwidth saving the knobs
    exist for.  An instruction qualifies when its ``op_name`` metadata
    path contains one of ``scopes``."""
    offenders = []
    for line in hlo_text.splitlines():
        if _INT8_CONVERT_RE.search(line) is None:
            continue
        op = _OP_NAME_IN_LINE_RE.search(line)
        path = op.group(1) if op else ""
        if not any(s in path for s in scopes):
            offenders.append(line.strip())
    if offenders:
        return [Finding("int8-promotion", entry,
                        f"{len(offenders)} float convert(s) of int8 "
                        "operands outside the fused dequant scope "
                        "(quantized weights silently re-materialized in "
                        "full precision):\n" + "\n".join(offenders[:8]))]
    return []


_OP_NAME_IN_LINE_RE = re.compile(r'op_name="([^"]+)"')


def dims_of(shape_string: str) -> str:
    """``"bf16[512,512]"`` -> ``"512,512"`` (idempotent on bare dims)."""
    m = re.search(r"\[([0-9,]*)\]", shape_string)
    return m.group(1) if m else shape_string


def collective_census(hlo_text: str) -> typing.Dict[str, int]:
    """Count of each collective op in the module (async pairs once)."""
    census = {op: 0 for op in COLLECTIVE_OPS}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        census[m.group(1)] += 1
    return census


_CUSTOM_CALL_TARGET_RE = re.compile(r'custom_call_target="([^"]+)"')


def custom_call_census(hlo_text: str) -> typing.Dict[str, int]:
    """Count of each ``custom_call_target`` in the module — Pallas/Mosaic
    kernels appear as ``tpu_custom_call``, so this says whether the kernel
    path (and not a dense fallback) is what was compiled."""
    census: typing.Dict[str, int] = {}
    for m in _CUSTOM_CALL_TARGET_RE.finditer(hlo_text):
        census[m.group(1)] = census.get(m.group(1), 0) + 1
    return census


#: one instruction line carrying a collective: the full result segment
#: (between '=' and the op name) is captured for byte accounting
_COLLECTIVE_LINE_RE = re.compile(
    r"=\s*([^=]*?)\s(" + "|".join(COLLECTIVE_OPS) + r")(-start|-done)?\(")

_SHAPE_TOKEN_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

#: XLA numbers the elements of a long tuple shape (``/*index=5*/``); the
#: ``=`` inside would cut the result segment short
_TUPLE_INDEX_RE = re.compile(r"/\*index=\d+\*/")

_REPLICA_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[^}]*(?:\},\{[^}]*)*\}\}|\[[0-9,]+\]<=\[[0-9,]+\]"
    r"(?:T\([0-9,]+\))?)")
_SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{([^}]*(?:\},\{[^}]*)*)\}")


def _parse_replica_groups(spec: str) -> typing.List[typing.List[int]]:
    """Both HLO spellings -> explicit groups.

    ``{{0,2},{1,3}}`` (explicit) and the iota form ``[2,4]<=[8]`` /
    ``[2,4]<=[4,2]T(1,0)`` (groups = transpose(reshape(arange(N), dims),
    perm).reshape(G, S))."""
    if spec.startswith("{"):
        return [[int(x) for x in grp.split(",") if x.strip() != ""]
                for grp in re.findall(r"\{([0-9,\s]*)\}", spec) if grp.strip()]
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", spec)
    if m is None:
        return []
    gshape = [int(x) for x in m.group(1).split(",")]
    rdims = [int(x) for x in m.group(2).split(",")]
    ids = np.arange(int(np.prod(rdims))).reshape(rdims)
    if m.group(3):
        ids = ids.transpose([int(x) for x in m.group(3).split(",")])
    return ids.reshape(gshape).tolist()


def group_axes(groups: typing.Sequence[typing.Sequence[int]],
               mesh_shape: typing.Mapping[str, int]) -> typing.Tuple[str, ...]:
    """Which mesh axes a replica-group set communicates over.

    Device/partition ids are positions in the mesh's device array flattened
    in axis order (how jax assigns logical ids), so ``unravel_index`` maps
    each member to mesh coordinates; an axis the members DIFFER on is an
    axis the collective moves data across.  ``mesh_shape`` must be the
    ordered axis -> size mapping of the audited mesh."""
    axes = list(mesh_shape)
    sizes = [mesh_shape[a] for a in axes]
    varying: typing.Set[str] = set()
    for grp in groups:
        if len(grp) < 2:
            continue
        coords = np.asarray([np.unravel_index(i, sizes) for i in grp])
        for k, a in enumerate(axes):
            if len(set(coords[:, k].tolist())) > 1:
                varying.add(a)
    return tuple(a for a in axes if a in varying)


def _pairs_axes(pairs_text: str, mesh_shape: typing.Mapping[str, int]
                ) -> typing.Tuple[str, ...]:
    """Axes of a ``source_target_pairs`` permute (each pair one group)."""
    pairs = re.findall(r"\{?\s*(\d+)\s*,\s*(\d+)\s*\}?", pairs_text)
    return group_axes([[int(a), int(b)] for a, b in pairs], mesh_shape)


def _result_bytes(result_segment: str, async_start: bool) -> int:
    """Bytes of a collective's result shapes.

    Sync ops: sum every array in the (possibly tuple) result — variadic
    all-reduces list one shape per operand.  Async ``-start`` tuples
    interleave operand and result aliases ``(in..., out..., ctx)``; summing
    would double-count, so take the LARGEST array (equals the shape for
    all-reduce, the gathered output for all-gather)."""
    sizes = [int(np.prod([int(d) for d in dims.split(",") if d]))
             * _DTYPE_BYTES.get(dt, 1)
             for dt, dims in _SHAPE_TOKEN_RE.findall(result_segment)]
    if not sizes:
        return 0
    return max(sizes) if async_start else sum(sizes)


def collective_inventory(hlo_text: str,
                         mesh_shape: typing.Optional[
                             typing.Mapping[str, int]] = None
                         ) -> typing.Dict[str, dict]:
    """Per-kind ``{"count", "bytes"[, "axes"]}`` census of one compiled
    module — the ONE census shared by ``scripts/pod_lowering.py`` reports,
    the dryrun MULTICHIP rows, and the mesh-budget audit, so they can never
    disagree on a count.  Counting conventions match
    :func:`collective_census` exactly (sync once, async pairs once via the
    ``-start`` twin; ``-done`` ignored).

    ``bytes``: result-shape bytes per :func:`_result_bytes` — a consistent
    *metric*, not a wire model (an all-gather's result counts the gathered
    array once; per-link traffic differs per algorithm).

    With ``mesh_shape`` (ordered axis -> size of the audited mesh) each
    kind also carries ``"axes"``: counts keyed by the ``+``-joined mesh
    axes its replica groups / permute pairs span — the attribution that
    lets a budget failure NAME the axis a surplus collective reshards
    over."""
    inv: typing.Dict[str, dict] = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_LINE_RE.search(_TUPLE_INDEX_RE.sub("", line))
        if m is None:
            continue
        result_seg, kind, suffix = m.groups()
        if suffix == "-done":
            continue
        entry = inv.setdefault(kind, {"count": 0, "bytes": 0})
        entry["count"] += 1
        entry["bytes"] += _result_bytes(result_seg, suffix == "-start")
        if mesh_shape is None:
            continue
        axes: typing.Tuple[str, ...] = ()
        g = _REPLICA_GROUPS_RE.search(line)
        if g is not None:
            axes = group_axes(_parse_replica_groups(g.group(1)), mesh_shape)
        else:
            p = _SOURCE_TARGET_RE.search(line)
            if p is not None:
                axes = _pairs_axes(p.group(1), mesh_shape)
        key = "+".join(axes) if axes else "none"
        per_axes = entry.setdefault("axes", {})
        per_axes[key] = per_axes.get(key, 0) + 1
    return inv


def collective_budget_audit(entry: str,
                            census: typing.Mapping[str, int],
                            budget: typing.Mapping[str, int]
                            ) -> typing.List[Finding]:
    """Census within budget; an op missing from the budget is budget 0 (a
    NEW collective kind appearing is exactly the regression this catches)."""
    findings = []
    for op, n in sorted(census.items()):
        cap = int(budget.get(op, 0))
        if n > cap:
            findings.append(Finding(
                "collective-budget", entry,
                f"{n} x {op} (budget {cap}) — an unbudgeted collective "
                "usually means accidental resharding; if the comms are "
                "intentional, raise the budget in analysis/budgets.json "
                "with a PR note"))
    return findings


def host_sync_audit(entry: str, hlo_text: str) -> typing.List[Finding]:
    """No host round-trips compiled into the module: infeed/outfeed/send/
    recv ops or callback-flavored custom-call targets."""
    offenders = []
    for line in hlo_text.splitlines():
        m = _HOST_OP_RE.search(line)
        if m is not None and m.group(2) is None:  # count start/sync once
            offenders.append(f"{m.group(1)}: {line.strip()[:120]}")
            continue
        c = _HOST_CALLBACK_RE.search(line)
        if c is not None:
            offenders.append(f"custom-call {c.group(1)}: "
                             f"{line.strip()[:120]}")
    if offenders:
        return [Finding("host-sync", entry,
                        f"{len(offenders)} host-sync op(s) compiled into a "
                        "hot path:\n" + "\n".join(offenders[:8]))]
    return []


# ---- budgets + one-call audit ---------------------------------------------

def load_budgets(path: typing.Optional[str] = None) -> dict:
    with open(path or BUDGETS_PATH) as f:
        return json.load(f)


def audit(entry: str, hlo_text: str, *,
          expected_aliases: typing.Optional[int] = None,
          protected_shapes: typing.Optional[typing.Set[str]] = None,
          max_copied_bytes: int = 0,
          bf16_param_shapes: typing.Optional[typing.Set[str]] = None,
          promotion_allow: typing.Collection[str] = (),
          budget: typing.Optional[typing.Mapping[str, int]] = None,
          check_host_sync: bool = True) -> typing.List[Finding]:
    """Run every applicable pass over one compiled module.  ``None``
    disables a pass (the caller knows which invariants its entry point
    promises); the budget defaults to all-zero when a mapping is given."""
    findings: typing.List[Finding] = []
    if expected_aliases is not None:
        findings += donation_audit(entry, hlo_text, expected_aliases)
    if protected_shapes:
        findings += big_copy_audit(entry, hlo_text, protected_shapes,
                                   max_copied_bytes)
    if bf16_param_shapes:
        findings += dtype_promotion_audit(entry, hlo_text, bf16_param_shapes,
                                          promotion_allow)
    if budget is not None:
        findings += collective_budget_audit(
            entry, collective_census(hlo_text), budget)
    if check_host_sync:
        findings += host_sync_audit(entry, hlo_text)
    # always on: vacuously clean on int8-free modules, and the quantized
    # path (serve_quantized_weights) gets its no-promotion-outside-dequant
    # invariant audited for free the moment an entry point compiles with
    # int8 weights
    findings += int8_promotion_audit(entry, hlo_text)
    return findings
