#!/usr/bin/env python3
"""Compiled Pallas kernels against their XLA references, on the device.

The kernel tests (tests/flash_attention_test.py, tests/map_mixer_test.py)
run the kernel BODIES in interpret mode on the CPU; what Mosaic makes of them
only exists on a chip.  This runs the two kernels the shipped configs train
through — flash attention forward+backward (parallel/flash_attention.py) and
the learned-map mixer forward+backward (parallel/map_mixer.py) — through
their public dispatchers, compiled for the local device, against the in-tree
dense references evaluated in float32 under
``jax.default_matmul_precision("highest")``.

Prints one JSON line per kernel and a final ``{"ok": ...}`` line; exits 1 if
any comparison exceeds :data:`TOLERANCE`, or if a dispatcher took the dense
path on an accelerator (no ``tpu_custom_call`` in the compiled module).

A third leg holds the gated delta rule's triangular solve
(parallel/delta_solve.py, float32) and its backward, at the Olmo-Hybrid cell's
shape ``[1, 256, 10, 64, 64]`` on near-coincident keys, to the host's float64
inverse: no system further off than :data:`SOLVE_TOLERANCE` of its largest
entry AND, in the mean over the systems, than twice what XLA's blocked form
(``model/gated_delta.py _blocked_inverse``, twelve float32 ``highest``
matmuls) is off on the same chip — a product with a dropped bfloat16 term
fails the second.

A fourth leg holds the windowed forward's two forms — the band kernel
(``_fwd_band``: one-pass softmax over a q tile's whole band) and the tiled
one the predicate falls back to (``_fwd_flat`` at ``window_block`` tiles,
online softmax) — at the Laguna cell's shape ``[2, 8192, 72, 128]``, window
512, to the host's float64 band: ``out`` inside :data:`TOLERANCE`, ``lse``
inside :data:`LSE_TOLERANCE`, and the band form no further off than twice
the tiled one.

A fifth leg holds layer ``mamba``'s chunked scan (parallel/ssd_scan.py,
through ``model/mamba.py ssd``) at the Granite cell's shapes — ``x [1, 8192,
64, 64]``, state 128, chunk 256, decays as strong as the cell's — to the XLA
form ``ssd_xla`` in float32 under ``highest``: ``y`` and the five gradients
inside :data:`TOLERANCE`, beside what the XLA form in bfloat16 (the parent's
path) is off against the same reference, and each path's time a call.

A sixth leg holds layer ``gated_delta``'s chunked rule
(parallel/delta_rule.py, through ``model/gated_delta.py kernel_rule``: XLA's
``T`` around the solve's pair, then the rule's pair) at the Olmo-Hybrid
cell's shapes — ``q`` / ``k [1, 16384, 30, 96]``, ``v [.., 192]``, chunk 64,
``beta`` up to 2, the layer's own decays — to the XLA form ``grouped_rule``
in float32 under ``highest``: ``o`` and the five gradients no further off
than half as much again as the XLA form in bfloat16 (the parent's path) is
off against the same reference on the same chip (or :data:`TOLERANCE`, if
that is more: with keys that share a direction and ``beta`` up to 2 the
solved transform reaches 3, ``W``, ``U``, ``V'`` and the carried state's
bfloat16 copy each round once more, and the XLA form itself is 2-8% of the
largest entry off the float32 one); each path's time a call.

A seventh leg holds layer ``kda``'s chunked rule — a delta rule with a
log-decay a CHANNEL of the key (parallel/kda_rule.py, through ``model/kda.py
kernel_rule``: the scores' pair with the norms of ``q`` and ``k`` and the
running sum of ``g`` inside, XLA's ``diag(beta)`` round the solve's pair, the
walk's pair) — at the Kimi-Linear cell's shapes — ``q`` / ``k`` / ``v [1,
16384, 32, 128]``, chunk 64, ``beta = sigmoid(.)``, ``g = -exp(A_log)
softplus(. + dt_bias)`` a channel with ``A_log`` and ``dt_bias`` seeded as the
layer draws them (a chunk's cumulative log-decay reaches -100 and beyond) — to
the XLA form ``grouped_rule`` in float32 under ``highest``: ``o`` and the five
gradients no further off than :data:`KDA_RULE_ROOM` x what the XLA form in
bfloat16 (the parent's path) is off against the same reference on the same
chip (:data:`KDA_RULE_ROOM_REHEARSAL` at a CPU rehearsal's size), or :data:`KDA_RULE_FLOOR` of the largest entry where that is more (an
output both forms hold to a few 2^-9 says nothing of either); each path's time
a call.

An eighth leg, run only by ``--only-select``, holds the selected flash kernels
(``flash_fwd_select``, ``flash_bwd_dq_select``, ``flash_bwd_dkv_select`` of
parallel/flash_attention.py) in both forms at their cells' shapes: ``block`` 1
(Keye-VL-2.0: 32 query over 4 K/V heads x 16,384 x 128, a seeded top-2,048
choice of keys a query, one for all heads) and ``block`` 64 (MiniCPM-SALA: 16
over 1, ``model/sparse.py``'s own selection on the seeded operands).  Each
kernel's ms a call from the device trace, its us a live cell and a 512 x 512
cell's worth of pairs, and ``out`` / ``lse`` / dq / dk / dv of ONE query head
against ``_xla_select_with_lse`` in float32 ``highest``.

A ninth leg, run only by ``--only-index-loss``, holds the learned indexer's
index-loss kernel (``index_loss_pass`` of parallel/index_loss.py) ALONE at
the Keye-VL-2.0 cell's shape — 1 x 16,384, 32 query over 4 K/V heads of 128,
16 index heads of 64, ``model/indexer.py top_keys``' choice of 2,048 seeded
keys a query as bits, the selected forward's own ``lse`` — beside the XLA
form ``model/indexer.py xla_index_loss``: each's ms a call from the device
trace, and the value, the largest kept score and the three gradients of both
against the XLA form in float32 ``highest``, the kernel no further off than
:data:`INDEX_LOSS_ROOM` x what the XLA form is (or :data:`INDEX_LOSS_FLOOR`
of the largest entry); and what a float32 ``dot`` at the default precision
carries on the device (the parent's two gradient contractions).

A tenth leg, run only by ``--only-flash-forward``, holds the causal flash
FORWARD (``flash_fwd_causal``: ``_fwd_flat`` at ``call_tiles``' 1,024 x 2,048
tiles) ALONE at the six shapes the train cells hand it
(:data:`FLASH_FORWARD_SHAPES`): its ms a call from the device trace, the
online-softmax steps of a call and its us a step, and ``out`` / ``lse`` of one
head against ``_xla_reference_with_lse`` in float32 ``highest`` inside
:data:`TOLERANCE` / :data:`LSE_TOLERANCE`.

An eleventh leg, run only by ``--only-flash-backward``, holds the flash
BACKWARD's three forms (``flash_attention.BACKWARD_FORMS``) — the one-pass
kernel with a head's dk / dv resident (``_bwd_flat_one_pass``: q blocks
outermost, dq in VMEM over the k walk), the same with its dq resident (k
blocks outermost, dk / dv over the q walk; no partials in HBM either way) and
the split dq / dk-dv pair (``_bwd_flat_split``) — at the shapes the train
cells hand it (:data:`FLASH_BACKWARD_SHAPES`: causal, block-diffusion and
windowed), and which of them ``backward_form`` picks:
each form's ms a call from the device trace, and dq, dk, dv of ONE head
against the dense form's gradients in float32 ``highest``: a one pass
inside :data:`TOLERANCE` and no further off than 1.25 x the split pair.  A
form Mosaic refuses at a shape (dk / dv resident at head width 512) is named
under ``refused``, and fails the leg only where it is the form picked.

Shapes: flash at the long-context recipe's per-chip shape (seq 16,384, head
dim 128; two heads so the dense reference's [s, s] scores fit beside it);
the mixer at the flagship's (8 heads, seq 512, 512 features/head, batch 32).
Operands are bfloat16, the dtype both recipes compute in.
"""
import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

#: bound on max|kernel - reference| / max|reference|, per output and per
#: gradient.  bfloat16 keeps 8 significand bits, so one rounding is at most
#: 2^-9 relative.  Each kernel rounds the tile it feeds the MXU (the
#: probabilities P, then dS in the backward) and rounds what it writes back,
#: and a gradient chains at most four such roundings: a worst-case element
#: is off by about 2^-7 of the tensor's largest magnitude.  2^-6 leaves a
#: factor of two; a wrong block index, mask or scale is an error of order
#: one (>= 2^-2), forty times the bound.
TOLERANCE = 2.0 ** -6


#: the kda rule's kernels against the XLA form in bfloat16, an output's error
#: over the other's on the same operands: the pairs round where the XLA form
#: rounds (matmul operands in bfloat16, ``gamma``, the solve's input and the
#: carried state float32), so the two are one rounding's luck apart: the
#: largest of 67 M entries at the cell's size, where that luck is a few
#: percent; a CPU rehearsal's toy size reads a quarter either way
KDA_RULE_ROOM = 1.1
KDA_RULE_ROOM_REHEARSAL = 1.5
#: below this share of the reference's largest entry an output is held by
#: both forms and the ratio of two roundings is not read (2^-8: one bfloat16
#: rounding of the largest entry)
KDA_RULE_FLOOR = 2.0 ** -8


#: the index-loss kernel against the XLA form, an output's error over the
#: other's against float32 ``highest`` on the same operands (PR 59's rule for
#: the kda rule): both round the matmuls' operands to bfloat16 and keep every
#: plane float32, so they are an order of summation apart
INDEX_LOSS_ROOM = 1.1
#: below this share of the reference's largest entry both forms hold an
#: output and the ratio of two roundings is not read
INDEX_LOSS_FLOOR = 2.0 ** -12


#: the solve's bound against float64, as a share of the largest entry: what
#: tests/delta_solve_test.py holds the kernels and the XLA form to on the CPU
SOLVE_TOLERANCE = 2e-5


def _errors(got, want):
    """{name: max|got - want| / max|want|} over matching tuples of arrays."""
    import numpy as np
    out = {}
    for name, g, w in zip(("out", "d0", "d1", "d2", "d3", "d4"), got, want):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        assert g.shape == w.shape, (name, g.shape, w.shape)
        if not (np.isfinite(g).all() and np.isfinite(w).all()):
            out[name] = float("inf")
        else:
            out[name] = float(np.abs(g - w).max() / np.abs(w).max())
    return out


def _with_grads(f):
    """``(cotangent, *operands) -> (out, *operand gradients)`` of ``f``."""
    import jax

    def run(ct, *xs):
        out, vjp = jax.vjp(f, *xs)
        return (out,) + tuple(vjp(ct))
    return jax.jit(run)


def _run(name, fn, ref_fn, operands, cotangent_seed):
    """Compile ``fn`` (value + vjp) for the local device, run it once, and
    compare with ``ref_fn`` on float32 copies of the operands."""
    import jax
    import jax.numpy as jnp

    shape = jax.eval_shape(fn, *operands)
    # the same (already rounded) cotangent feeds both sides
    ct = jax.random.normal(jax.random.PRNGKey(cotangent_seed), shape.shape,
                           jnp.float32).astype(shape.dtype)
    compiled = _with_grads(fn).lower(ct, *operands).compile()
    from homebrewnlp_tpu.analysis import hlo_lint
    kernels = hlo_lint.custom_call_census(compiled.as_text()).get(
        "tpu_custom_call", 0)
    got = compiled(ct, *operands)
    with jax.default_matmul_precision("highest"):
        want = ref_fn(*[x.astype(jnp.float32) for x in (ct, *operands)])
    errs = _errors(got, want)
    ok = all(e <= TOLERANCE for e in errs.values())
    platform = jax.devices()[0].platform
    if platform != "cpu" and not kernels:
        ok = False  # the dispatcher handed an accelerator the dense path
    row = {"kernel": name, "ok": ok, "tpu_custom_calls": kernels,
           "implementation": "pallas" if kernels else "dense",
           "max_err_over_max_ref": {k: round(v, 6) for k, v in errs.items()},
           "tolerance": TOLERANCE,
           "shapes": [list(x.shape) for x in operands],
           "dtype": str(operands[0].dtype)}
    print(json.dumps(row), flush=True)
    return ok


def _solve_leg(shape=(1, 256, 10, 64, 64)) -> bool:
    """The solve's kernel pair against float64 on the host, beside XLA's
    blocked form on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.model import gated_delta
    from homebrewnlp_tpu.parallel import delta_solve

    l, matrices = shape[-1], int(np.prod(shape[:-2]))
    rng = np.random.default_rng(37)
    # keys of a chunk nearly alike: entries of N = 2 k_i . k_j near 1.8
    keys = rng.normal(size=(matrices, 1, 8)) \
        + 0.3 * rng.normal(size=(matrices, l, 8))
    keys /= np.linalg.norm(keys, axis=-1, keepdims=True)
    strict = np.tril(2.0 * np.einsum("nid,njd->nij", keys, keys), -1
                     ).astype(np.float32)
    ct = rng.normal(size=strict.shape).astype(np.float32)
    inv64 = np.linalg.inv(np.eye(l) + strict.astype(np.float64))
    inv_t = np.swapaxes(inv64, -1, -2)
    d64 = np.tril(-(inv_t @ ct.astype(np.float64) @ inv_t), -1)

    def both(inverse, backward):
        def run(n, g):
            inv = inverse(n)
            return inv, backward(inv, g)
        return jax.jit(run)

    platform = jax.devices()[0].platform
    applies = delta_solve.solve_kernel_applies(l, matrices)
    interpret = platform == "cpu"
    kernel = both(functools.partial(delta_solve.inverse_unit_lower,
                                    interpret=interpret),
                  functools.partial(delta_solve.inverse_unit_lower_bwd,
                                    interpret=interpret))
    blocked = both(gated_delta._blocked_inverse, gated_delta._xla_inverse_bwd)
    args = (jnp.asarray(strict.reshape(shape)), jnp.asarray(ct.reshape(shape)))

    def off(got, want):      # a system's largest error over its largest entry
        return np.abs(got - want).max((-1, -2)) / np.abs(want).max((-1, -2))

    errs = {}
    for name, fn in (("kernel", kernel), ("xla", blocked)):
        inv, d = (np.asarray(t, np.float64).reshape(strict.shape)
                  for t in fn(*args))
        errs[name] = {"upper": float(np.abs(np.triu(inv, 1)).max())}
        for part, got, want in (("inverse", inv, inv64),
                                ("backward", d, d64)):
            errs[name][part] = float(off(got, want).max())
            errs[name][part + "_mean"] = float(off(got, want).mean())
    ok = (applies or platform == "cpu") and errs["kernel"]["upper"] == 0.0
    for part in ("inverse", "backward"):
        ok = ok and errs["kernel"][part] <= SOLVE_TOLERANCE \
            and errs["kernel"][part + "_mean"] \
            <= 2 * errs["xla"][part + "_mean"]
    print(json.dumps({"kernel": "delta_solve", "ok": bool(ok),
                      "implementation": "pallas" if applies else
                      "pallas (interpret)", "max_err_over_max_ref": errs,
                      "tolerance": SOLVE_TOLERANCE, "shapes": [list(shape)],
                      "dtype": "float32"}), flush=True)
    return bool(ok)


#: bound on max|lse - float64|: both forms keep the statistics in float32
#: over scores the MXU accumulates from 128 exact bfloat16 products — on a
#: v5e that accumulation is off by 1.1e-4 at most at this shape, in both
#: forms alike (my chip run, PR 41) — while one key of 512 dropped or let in
#: moves a row's lse by about 2e-3
LSE_TOLERANCE = 5e-4


def _band_leg(shape=(2, 8192, 72, 128), window: int = 512) -> bool:
    """The windowed forward as a band kernel and as the tiled kernel, both
    against float64 on the host, at the Laguna cell's shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.parallel import flash_attention as flash

    b, s, heads, d = shape
    bh, scale = b * heads, d ** -0.5
    interpret = jax.devices()[0].platform == "cpu"
    q, k, v = (jax.random.normal(jax.random.PRNGKey(seed), (bh, s, d),
                                 jnp.float32).astype(jnp.bfloat16)
               for seed in (41, 42, 43))
    q64, k64, v64 = (np.asarray(t, np.float64) for t in (q, k, v))
    out64, lse64 = np.empty((bh, s, d)), np.empty((bh, s))
    step = min(512, s)
    for q0 in range(0, s, step):       # the band, a strip of queries at a time
        k0 = max(q0 - window + 1, 0)
        score = scale * q64[:, q0:q0 + step] @ k64[:, k0:q0 + step
                                                   ].transpose(0, 2, 1)
        back = np.arange(q0, q0 + step)[:, None] - np.arange(k0, q0 + step)
        score = np.where((back >= 0) & (back < window), score, -np.inf)
        m = score.max(-1, keepdims=True)
        p = np.exp(score - m)
        l = p.sum(-1, keepdims=True)
        out64[:, q0:q0 + step] = (p / l) @ v64[:, k0:q0 + step]
        lse64[:, q0:q0 + step] = (m + np.log(l))[..., 0]

    applies = flash.band_applies(s, d, window, q.dtype.itemsize)
    blk = flash.window_block(s, window)
    forms = {"band": jax.jit(lambda q, k, v: flash._fwd_band(
                 q, k, v, scale, flash.band_block(s), window, interpret)),
             "tiled": jax.jit(lambda q, k, v: flash._fwd_flat(
                 q, k, v, scale, True, blk, blk, interpret, window=window))}
    errs = {}
    for name, fn in forms.items():
        out, lse = fn(q, k, v)
        errs[name] = {
            "out": float(np.abs(np.asarray(out, np.float64) - out64).max()
                         / np.abs(out64).max()),
            "lse": float(np.abs(np.asarray(lse, np.float64) - lse64).max())}
    ok = applies and errs["band"]["out"] <= TOLERANCE \
        and errs["band"]["lse"] <= LSE_TOLERANCE \
        and errs["band"]["out"] <= 2 * errs["tiled"]["out"] \
        and errs["band"]["lse"] <= 2 * max(errs["tiled"]["lse"], 1e-6)
    print(json.dumps({"kernel": "flash_fwd_window", "ok": bool(ok),
                      "implementation": "pallas (interpret)" if interpret
                      else "pallas", "band_applies": bool(applies),
                      "max_err_over_max_ref": errs, "tolerance": TOLERANCE,
                      "lse_tolerance": LSE_TOLERANCE,
                      "shapes": [list(shape)], "window": window,
                      "dtype": "bfloat16"}), flush=True)
    return bool(ok)


def _errors_and_ms(forms, ct, operands, want, calls: int):
    """``({name: errors against want}, {name: ms a call forward + backward})``
    of each ``(name, fn)``, compiled for the local device."""
    import time

    import jax

    errs, ms = {}, {}
    for name, fn in forms:
        compiled = _with_grads(fn).lower(ct, *operands).compile()
        got = jax.block_until_ready(compiled(ct, *operands))
        errs[name] = {k: round(v, 6) for k, v in _errors(got, want).items()}
        start = time.perf_counter()
        for _ in range(calls):
            got = compiled(ct, *operands)
        jax.block_until_ready(got)
        ms[name] = round((time.perf_counter() - start) * 1000 / calls, 3)
        del got, compiled
    return errs, ms


def _scan_leg(s: int = 8192, heads: int = 64, p: int = 64, n: int = 128,
              chunk: int = 256) -> bool:
    """The scan's kernel pair and the XLA form in bfloat16, both against the
    XLA form in float32 ``highest`` on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.model import mamba
    from homebrewnlp_tpu.parallel import ssd_scan

    rng = np.random.default_rng(48)
    # the layer's own ranges: dt = softplus(.) log-uniform about [1e-3, 0.1]
    # with a tail, A = -U[1, 16]: a chunk's cumulative dt A reaches -400
    x, b_mat, c_mat = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                       for shape in ((1, s, heads, p), (1, s, n), (1, s, n)))
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.2),
                                        (1, s, heads))), jnp.float32)
    a = jnp.asarray(-rng.uniform(1.0, 16.0, (heads,)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(1, s, heads, p)), jnp.float32)
    operands = (x, dt, a, b_mat, c_mat)
    platform = jax.devices()[0].platform
    applies = ssd_scan.ssd_kernel_applies(s, chunk, heads, p, n)

    def kernel(*args):
        if platform != "cpu":
            return mamba.ssd(*args, chunk)[0]       # the layer's dispatcher
        return ssd_scan.ssd_scan(
            args[0], args[1], ssd_scan.log_decay(args[1], args[2], chunk),
            *args[3:], chunk, None, True)

    def xla(*args):
        return mamba.ssd_xla(*args, chunk)[0]

    low = float(jnp.min(ssd_scan.log_decay(dt, a, chunk)))
    with jax.default_matmul_precision("highest"):
        want = _with_grads(xla)(ct, *(t.astype(jnp.float32)
                                      for t in operands))
    errs, ms = _errors_and_ms((("kernel", kernel), ("xla", xla)), ct,
                              operands, want, 5)
    ok = (applies or platform == "cpu") and all(
        e <= TOLERANCE for e in errs["kernel"].values())
    print(json.dumps({"kernel": "ssd_scan", "ok": bool(ok),
                      "implementation": "pallas" if applies else
                      "pallas (interpret)", "max_err_over_max_ref": errs,
                      "tolerance": TOLERANCE, "log_decay_min": low,
                      "ms_a_call_forward_and_backward": ms,
                      "shapes": [list(t.shape) for t in operands],
                      "chunk": chunk, "dtype": "bfloat16"}), flush=True)
    return bool(ok)


def _rule_leg(s: int = 16384, heads: int = 30, dk: int = 96, dv: int = 192,
              chunk: int = 64) -> bool:
    """The rule's kernel pair and the XLA form in bfloat16, both against the
    XLA form in float32 ``highest`` on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.model import gated_delta
    from homebrewnlp_tpu.parallel import delta_rule

    rng = np.random.default_rng(50)
    shared = rng.normal(size=(1, 1, heads, dk))

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    # the layer's own ranges: unit keys that share a direction, beta = 2
    # sigmoid(.), g = -A softplus(.) with A = U(0, 16) a head and softplus(.)
    # log-uniform in [1e-3, 1e-1]
    q, k, v = (jnp.asarray(t, jnp.bfloat16) for t in (
        unit(rng.normal(size=(1, s, heads, dk)) + shared) * dk ** -0.5,
        unit(rng.normal(size=(1, s, heads, dk)) + 2 * shared),
        rng.normal(size=(1, s, heads, dv))))
    beta = jnp.asarray(rng.uniform(0.0, 2.0, (1, s, heads)), jnp.float32)
    g = jnp.asarray(-rng.uniform(0.0, 16.0, (heads,)) * np.exp(rng.uniform(
        np.log(1e-3), np.log(1e-1), (1, s, heads))), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(1, s, heads, dv)), jnp.float32)
    operands = (q, k, v, beta, g)
    platform = jax.devices()[0].platform
    applies = delta_rule.rule_kernel_applies(chunk, heads, dk, dv, s)
    if platform == "cpu":
        for name in ("delta_rule_pair", "delta_strict"):
            setattr(gated_delta, name, functools.partial(
                getattr(delta_rule, name), interpret=True))

    def kernel(*args):
        return gated_delta.kernel_rule(*args, chunk)[0].astype(jnp.float32)

    def xla(*args):
        return gated_delta.grouped_rule(*args, chunk)[0].astype(jnp.float32)

    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(_with_grads(xla)(
            ct, *(t.astype(jnp.float32) for t in operands)))
    errs, ms = _errors_and_ms((("kernel", kernel), ("xla", xla)), ct,
                              operands, want, 3)
    ok = (applies or platform == "cpu") and all(
        e <= max(TOLERANCE, 1.5 * errs["xla"][name])
        for name, e in errs["kernel"].items())
    print(json.dumps({"kernel": "delta_rule", "ok": bool(ok),
                      "implementation": "pallas" if applies else
                      "pallas (interpret)", "max_err_over_max_ref": errs,
                      "tolerance": "1.5 x the XLA form's",
                      "ms_a_call_forward_and_backward": ms,
                      "shapes": [list(t.shape) for t in operands],
                      "chunk": chunk, "dtype": "bfloat16"}), flush=True)
    return bool(ok)


def _kda_rule_leg(s: int = 16384, heads: int = 32, dk: int = 128,
                  dv: int = 128) -> bool:
    """Layer ``kda``'s kernel pairs and the XLA form in bfloat16, both
    against the XLA form in float32 ``highest`` on the same device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.model import kda
    from homebrewnlp_tpu.parallel import kda_rule

    chunk = min(kda.CHUNK, s)
    rng = np.random.default_rng(59)
    shared = rng.normal(size=(1, 1, heads, dk))

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    def some_length(x):
        return unit(x) * rng.uniform(0.5, 2.0, size=x.shape[:-1] + (1,))

    # the layer's own ranges at its seeded start: keys that share a
    # direction and, like the queries, are not of unit length (the rule
    # normalises what the conv left), beta = sigmoid(.), g = -exp(A_log)
    # softplus(. + dt_bias) with exp(A_log) = U(1, 16) a head,
    # softplus(dt_bias) log-uniform in [1e-3, 1e-1] a channel and the
    # low-rank pair's part beside it
    q, k, v = (jnp.asarray(t, jnp.bfloat16) for t in (
        some_length(rng.normal(size=(1, s, heads, dk)) + shared),
        some_length(rng.normal(size=(1, s, heads, dk)) + 2 * shared),
        rng.normal(size=(1, s, heads, dv))))
    beta = jnp.asarray(1 / (1 + np.exp(-rng.normal(size=(1, s, heads)))),
                       jnp.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), (heads, dk)))
    dt_bias = dt + np.log(-np.expm1(-dt))           # softplus's inverse
    raw = 0.3 * rng.normal(size=(1, s, heads, dk)) + dt_bias
    g = jnp.asarray(-rng.uniform(1.0, 16.0, (heads, 1))
                    * np.logaddexp(raw, 0.0), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(1, s, heads, dv)), jnp.float32)
    operands = (q, k, v, beta, g)
    platform = jax.devices()[0].platform
    applies = kda_rule.kda_kernel_applies(chunk, heads, dk, dv, s)
    if platform == "cpu":
        for name in ("kda_rule_pair", "kda_scores"):
            setattr(kda, name, functools.partial(
                getattr(kda_rule, name), interpret=True))

    def kernel(*args):
        return kda.kernel_rule(*args, chunk)[0].astype(jnp.float32)

    def xla(*args):
        return kda.normalised(kda.grouped_rule)(*args, chunk)[0].astype(
            jnp.float32)

    low = float(jnp.min(jnp.sum(g.reshape(1, s // chunk, chunk, heads, dk),
                                axis=2)))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(_with_grads(xla)(
            ct, *(t.astype(jnp.float32) for t in operands)))
    errs, ms = _errors_and_ms((("kernel", kernel), ("xla", xla)), ct,
                              operands, want, 3)
    room = KDA_RULE_ROOM_REHEARSAL if platform == "cpu" else KDA_RULE_ROOM
    ok = (applies or platform == "cpu") and all(
        e <= max(KDA_RULE_FLOOR, room * errs["xla"][name])
        for name, e in errs["kernel"].items())
    print(json.dumps({"kernel": "kda_rule", "ok": bool(ok),
                      "implementation": "pallas" if applies else
                      "pallas (interpret)", "max_err_over_max_ref": errs,
                      "tolerance": f"{room} x the XLA form's, or "
                                   f"{KDA_RULE_FLOOR}",
                      "log_decay_min": low,
                      "ms_a_call_forward_and_backward": ms,
                      "shapes": [list(t.shape) for t in operands],
                      "chunk": chunk, "dtype": "bfloat16"}), flush=True)
    return bool(ok)


def _kernel_ms(run, pattern: str, calls: int = 3):
    """``{kernel: ms a call}`` of the device operations named ``pattern``
    over ``calls`` runs of ``run()`` under the profiler, and under ``wall``
    the host's clock round the same runs with the profiler off (all a CPU
    rehearsal has: its trace holds no device)."""
    import re
    import tempfile
    import time

    import jax

    from benchmark.trace import reduce

    jax.block_until_ready(run())
    start = time.perf_counter()
    for _ in range(calls):
        out = run()
    jax.block_until_ready(out)
    total = {"wall": (time.perf_counter() - start) * 1000 / calls}
    if jax.devices()[0].platform != "cpu":
        with tempfile.TemporaryDirectory() as trace_dir:
            with jax.profiler.trace(trace_dir):
                for _ in range(calls):
                    out = run()
                jax.block_until_ready(out)
            ops = reduce.load(reduce.newest_xplane(trace_dir)).devices[0].ops
        for op in ops:
            # under a vjp the op is ``transpose_jvp_<kernel>__.1``
            found = re.search(pattern, reduce.short_name(op.name))
            if found:
                total[found.group(0)] = total.get(found.group(0), 0.0) \
                    + op.seconds * 1000 / calls
    return {k: round(v, 3) for k, v in total.items()}


def _select_choice(block: int, q, k, topk: int, seed: int):
    """The choice a cell's layer would hand the kernels on seeded operands:
    ``block`` 1, ``model/indexer.py top_keys`` over seeded scores (exactly
    ``topk`` scattered keys a query past ``topk``), packed to bits; else
    ``model/sparse.py``'s own selection at MiniCPM-SALA's sizes."""
    import jax
    import jax.numpy as jnp

    from homebrewnlp_tpu.model import indexer, sparse
    from homebrewnlp_tpu.parallel import flash_attention as fa

    s = q.shape[1]
    if block != 1:
        sizes = sparse.Sizes(32, 16, block, min(64, s // block // 2), 1,
                             min(2048, s // 4), 0)
        return jax.jit(lambda q, k: sparse.select_blocks(
            q, k, sizes, q.shape[-1] ** -0.5))(q, k)
    rows = min(1024, s)
    pick = jax.jit(lambda key, first: fa.pack_keep(indexer.top_keys(
        jax.random.uniform(key, (1, rows, s)), first, topk)))
    keys = jax.random.split(jax.random.PRNGKey(seed), s // rows)
    return jnp.concatenate([pick(keys[n], n * rows)
                            for n in range(s // rows)], axis=1)[:, None]


def _live_cells(keep, tiles, block: int, heads: int) -> int:
    """The cells of a selected grid over all ``heads`` that run their body."""
    import numpy as np

    from homebrewnlp_tpu.parallel import flash_attention as fa
    num_k = keep.shape[2] * (fa.KEEP_WORD if block == 1 else 1) // tiles[1]
    fetch_k = np.asarray(fa._select_tables(keep, *tiles, block)[1])
    return int((fetch_k.reshape(-1, num_k) == np.arange(num_k)).sum()) \
        * heads // keep.shape[1]


def _select_leg(block: int, s: int, heads: int, kv_heads: int, d: int = 128,
                topk: int = 2048, other_tiles=()) -> bool:
    """The three selected kernels of one form at its cell's shape: ms a call
    by the device trace, us a live cell, and one query head against the
    dense masked form in float32."""
    import jax
    import jax.numpy as jnp

    from homebrewnlp_tpu.parallel import flash_attention as fa

    interpret = jax.devices()[0].platform == "cpu"
    scale = d ** -0.5
    q, k, v, ct = (jax.random.normal(jax.random.PRNGKey(63 + n),
                                     (1, s, h, d), jnp.float32
                                     ).astype(jnp.bfloat16)
                   for n, h in enumerate((heads, kv_heads, kv_heads, heads)))
    keep = _select_choice(block, q, k, min(topk, s // 4), 63)
    tq, tk = fa.select_tile(s, block)
    live = _live_cells(keep, (tq, tk), block, heads)

    def per_cell(ms):
        return {name: {"ms_a_call": t,
                       "us_a_live_cell": round(t * 1000 / live, 3),
                       "us_a_512x512_of_pairs": round(
                           t * 1000 / (live * tq * tk / 512 ** 2), 3)}
                for name, t in ms.items()}

    def library_ms():
        both = jax.jit(lambda q, k, v, ct: jax.vjp(
            lambda *t: fa.flash_select(*t, keep, scale, block, interpret),
            q, k, v)[1](ct))
        return _kernel_ms(lambda: both(q, k, v, ct),
                          r"flash_[a-z_]+?_select")

    ms = library_ms()
    # ONE query head and its K/V head against the dense masked form
    one = (q[:, :, :1], k[:, :, :1], v[:, :, :1], ct[:, :, :1])
    keep1 = keep[:, :1]
    out, lse = jax.jit(lambda q, k, v: fa._select_fwd_impl(
        q, k, v, keep1, scale, block, interpret))(*one[:3])
    got = (out, lse) + jax.jit(lambda q, k, v, ct: jax.vjp(
        lambda *t: fa.flash_select(*t, keep1, scale, block, interpret),
        q, k, v)[1](ct))(*one)
    with jax.default_matmul_precision("highest"):
        def dense(q, k, v):
            o, e = fa._xla_select_with_lse(q, k, v, keep1, scale, block)
            return o, jax.lax.stop_gradient(e)
        (want_out, want_lse), pull = jax.vjp(
            dense, *(t.astype(jnp.float32) for t in one[:3]))
        want = (want_out, want_lse) + pull((one[3].astype(jnp.float32),
                                            jnp.zeros_like(want_lse)))
    errs = dict(zip(("out", "lse", "dq", "dk", "dv"),
                    _errors(got, want).values()))
    ok = all(e <= TOLERANCE for e in errs.values())
    row = {"kernel": f"flash_select_block{block}", "ok": bool(ok),
           "implementation": "pallas (interpret)" if interpret else "pallas",
           "tiles": [tq, tk], "live_cells": live,
           "kernels": per_cell(ms),
           "max_err_over_max_ref": {n: round(e, 6) for n, e in errs.items()},
           "tolerance": TOLERANCE,
           "shapes": [list(t.shape) for t in (q, k, v, keep)],
           "dtype": "bfloat16"}
    print(json.dumps(row), flush=True)
    # the library's three kernels at other tiles than ``select_tile``'s
    chosen = fa.select_tile
    for tiles in other_tiles:
        if tiles == (tq, tk) or s % tiles[1] or (
                block != 1 and fa._KEEP_LANES % (tiles[1] // block)):
            continue
        fa.select_tile = lambda s, block: tiles
        try:
            cells = _live_cells(keep, tiles, block, heads)
            print(json.dumps({"library_at_tiles": list(tiles), "block": block,
                              "live_cells": cells, "ms_a_call": library_ms()}),
                  flush=True)
        except Exception as e:
            print(json.dumps({"library_at_tiles": list(tiles),
                              "refused": repr(e)[:400]}), flush=True)
        finally:
            fa.select_tile = chosen
    return bool(ok)


#: ``(the cells, batch x heads, positions, key width, value width)`` of the
#: causal forward's calls in the train cells
FLASH_FORWARD_SHAPES = (
    ("joyai_llm_flash, kimi_linear", 32, 16384, 192, 128),
    ("zaya1", 8, 16384, 128, 128),
    ("laguna (global layers)", 96, 8192, 128, 128),
    ("ouro", 32, 4096, 128, 128),
    ("1b_long_context", 16, 16384, 512, 512),
    ("granite", 32, 8192, 64, 64))
def _forward_steps(bh: int, s: int, bq: int, bk: int) -> int:
    """The online-softmax steps of a causal call: its live cells."""
    bq, bk = min(bq, s), min(bk, s)
    return bh * sum(1 for qi in range(s // bq) for ki in range(s // bk)
                    if ki * bk <= qi * bq + bq - 1)


def _flash_forward_leg(seq: int = 0) -> bool:
    """The causal forward alone at its cells' shapes (``seq``: every shape
    at that many positions and two heads, a CPU rehearsal's size)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.parallel import flash_attention as fa

    interpret = jax.devices()[0].platform == "cpu"
    ok = True

    def operands(bh, s, d, dv, seed=66):
        return tuple(jax.random.normal(jax.random.PRNGKey(seed + n),
                                       (bh, s, w), jnp.float32
                                       ).astype(jnp.bfloat16)
                     for n, w in enumerate((d, d, dv)))

    def timed(name, fn, steps, *args):
        ms = _kernel_ms(lambda: fn(*args), name)
        t_ms = ms.get(name, ms["wall"])
        return t_ms, round(t_ms * 1000 / steps, 3)

    for cells, bh, s, d, dv in FLASH_FORWARD_SHAPES:
        if seq:
            bh, s = min(bh, 2), seq
        scale = d ** -0.5
        q, k, v = operands(bh, s, d, dv)
        _, bq, bk, _ = fa.call_tiles(s, d, None, 2, dv)
        steps = _forward_steps(bh, s, bq, bk)
        library = jax.jit(lambda q, k, v: fa._fwd_flat(
            q, k, v, scale, True, bq, bk, interpret))
        t_ms, us = timed("flash_fwd_causal", library, steps, q, k, v)
        out, lse = library(q, k, v)
        # ONE head against the dense form in float32
        with jax.default_matmul_precision("highest"):
            want, want_lse = jax.jit(lambda q, k, v: (
                fa._xla_reference_with_lse(q, k, v, scale, True)))(
                *(t[:1, :, None].astype(jnp.float32) for t in (q, k, v)))
        want, want_lse = np.asarray(want[0, :, 0]), np.asarray(want_lse[0])
        errs = {"out": float(np.abs(np.asarray(out[0], np.float32) - want
                                    ).max() / np.abs(want).max()),
                "lse": float(np.abs(np.asarray(lse[0]) - want_lse).max())}
        good = errs["out"] <= TOLERANCE and errs["lse"] <= LSE_TOLERANCE
        ok &= good
        print(json.dumps({
            "kernel": "flash_fwd_causal", "ok": bool(good), "cells": cells,
            "implementation": "pallas (interpret)" if interpret else "pallas",
            "shape": {"bh": bh, "s": s, "d_k": d, "d_v": dv},
            "tiles": [min(bq, s), min(bk, s)], "steps_a_call": steps,
            "ms_a_call": t_ms, "us_a_step": us,
            "max_err_over_max_ref": {n: round(e, 7) for n, e in errs.items()},
            "tolerance": TOLERANCE, "lse_tolerance": LSE_TOLERANCE,
            "dtype": "bfloat16"}), flush=True)
    return bool(ok)


#: ``(the cells, batch x heads, positions, key width, value width, window,
#: block-diffusion step)`` of the backward's calls in the train cells
FLASH_BACKWARD_SHAPES = (
    ("1b_long_context", 16, 16384, 512, 512, None, None),
    ("joyai_llm_flash, kimi_linear", 32, 16384, 192, 128, None, None),
    ("nemotron", 16, 16384, 128, 128, None, None),
    ("ouro", 32, 4096, 128, 128, None, None),
    ("sdar", 64, 8192, 128, 128, None, 4),
    ("granite", 32, 8192, 64, 64, None, None),
    ("laguna (window layers)", 144, 8192, 128, 128, 512, None))


def _flash_backward_leg(seq: int = 0) -> bool:
    """The backward's three forms at its cells' shapes (``seq``: every shape
    at that many positions and two heads, a CPU rehearsal's size)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.parallel import flash_attention as fa

    interpret = jax.devices()[0].platform == "cpu"
    ok = True
    for cells, bh, s, d, dv, window, step in FLASH_BACKWARD_SHAPES:
        if seq:
            bh, s = min(bh, 2), seq
            window = None if window is None else min(window, s // 4)
        scale = d ** -0.5
        q, k, v, do = (jax.random.normal(jax.random.PRNGKey(68 + n),
                                         (bh, s, w), jnp.float32
                                         ).astype(jnp.bfloat16)
                       for n, w in enumerate((d, d, dv, dv)))
        if step is not None:
            # the first block's rows see no key: the caller's merge hands
            # them a zero cotangent
            do = do.at[:, :step].set(0)
        blk, fwd_q, fwd_k, band = fa.call_tiles(s, d, window, 2, dv)
        blk = min(blk, s)
        if band:
            fwd_q = fwd_k = blk
        out, lse = jax.jit(lambda q, k, v: fa._fwd_flat(
            q, k, v, scale, True, fwd_q, fwd_k, interpret, window=window,
            step=step))(q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1,
                        keepdims=True)
        args = (q, k, v, do, lse[..., None], delta)

        # ONE head's gradients from the dense form in float32
        def dense(q, k, v):
            if step is not None:
                return fa._xla_stepped_with_lse(q, k, v, scale, step)[0]
            return fa._xla_reference(q, k, v, scale, True, window)

        with jax.default_matmul_precision("highest"):
            want = jax.jit(lambda q, k, v, do: jax.vjp(dense, q, k, v)[1](
                do))(*(t[:1, :, None].astype(jnp.float32)
                       for t in (q, k, v, do)))
        want = [np.asarray(w[0, :, 0]) for w in want]
        one_pass = fa.one_pass_tiles(blk, blk, d, dv, True, window)
        picked = fa.backward_form(s, s, d, dv, blk, blk, 2, window=window)
        line = {"kernel": "flash_bwd", "cells": cells,
                "implementation": "pallas (interpret)" if interpret
                else "pallas",
                "shape": {"bh": bh, "s": s, "d_k": d, "d_v": dv,
                          "window": window, "step": step},
                "tiles": [blk, blk], "one_pass_tiles": list(one_pass),
                "backward_form": picked}
        forms = {"dkv_resident": (fa._bwd_flat_one_pass, one_pass),
                 "dq_resident": (functools.partial(fa._bwd_flat_one_pass,
                                                   dq_resident=True),
                                 one_pass),
                 "split": (fa._bwd_flat_split, (blk, blk))}
        assert tuple(forms) == fa.BACKWARD_FORMS
        got, errs, ms = {}, {}, {}
        for name, (form, tiles) in forms.items():
            run = jax.jit(lambda *a, form=form, tiles=tiles: form(
                *a, scale, True, *tiles, interpret, window=window,
                step=step))
            try:
                timed = _kernel_ms(lambda: run(*args),
                                   r"flash_bwd_(fused|dq|dkv)_[a-z]+")
            except Exception as e:  # Mosaic refuses the form: a finding
                line.setdefault("refused", {})[name] = repr(e)[:400]
                continue
            ms[name] = {n: t for n, t in timed.items() if n != "wall"} \
                or {"wall": timed["wall"]}
            got[name] = run(*args)
            errs[name] = {
                n: float(np.abs(np.asarray(g[0], np.float32) - w).max()
                         / np.abs(w).max())
                for n, g, w in zip(("dq", "dk", "dv"), got[name], want)}
        good = picked in errs and "split" in errs and all(
            e <= TOLERANCE and e <= 1.25 * errs["split"][n] + 1e-6
            for name in errs if name != "split"
            for n, e in errs[name].items())
        ok &= good
        line["max_abs_diff_to_split"] = {
            name: {n: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                            - b.astype(jnp.float32))))
                   for n, a, b in zip(("dq", "dk", "dv"), got[name],
                                      got["split"])}
            for name in got if name != "split" and "split" in got}
        line.update({
            "ok": bool(good), "ms_a_call": ms,
            "ms_a_call_total": {n: round(sum(t.values()), 3)
                                for n, t in ms.items()},
            "max_err_over_max_ref": {n: {g: round(e, 7) for g, e in
                                         es.items()}
                                     for n, es in errs.items()},
            "tolerance": TOLERANCE, "dtype": "bfloat16"})
        print(json.dumps(line), flush=True)
        del got, args, out, lse, delta
    return bool(ok)


def _module_ms(run, calls: int = 3) -> float:
    """ms a call of ``run()``'s whole device program by the device trace's
    module line (the host's clock on the CPU, whose trace holds no device)."""
    import tempfile
    import time

    import jax

    from benchmark.trace import reduce

    jax.block_until_ready(run())
    if jax.devices()[0].platform == "cpu":
        start = time.perf_counter()
        for _ in range(calls):
            out = run()
        jax.block_until_ready(out)
        return round((time.perf_counter() - start) * 1000 / calls, 3)
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                out = run()
            jax.block_until_ready(out)
        modules = reduce.load(reduce.newest_xplane(trace_dir)
                              ).devices[0].modules
    return round(sum(m.seconds for m in modules) * 1000 / calls, 3)


def _index_loss_operands(s: int, heads: int, kv_heads: int, f: int,
                         index_heads: int, d: int, topk: int):
    """The Keye-VL-2.0 cell's layer at its seeded start: bfloat16 attention
    and index operands, float32 weights times ``index_heads ** -0.5``, a
    seeded top-``topk`` choice of scattered keys as bits, and the selected
    forward's own ``lse`` over it."""
    import jax
    import jax.numpy as jnp

    from homebrewnlp_tpu.parallel import flash_attention as fa

    def normal(n, shape, dtype=jnp.bfloat16):
        return jax.random.normal(jax.random.PRNGKey(64 + n), shape,
                                 jnp.float32).astype(dtype)

    q, k, v = (normal(n, (1, s, h, f))
               for n, h in enumerate((heads, kv_heads, kv_heads)))
    q_index = normal(3, (1, s, index_heads, d))
    k_index = normal(4, (1, s, d))
    weight = normal(5, (1, s, index_heads), jnp.float32) * index_heads ** -0.5
    keep = _select_choice(1, q, k, min(topk, s // 4), 64)
    scale = f ** -0.5
    if jax.devices()[0].platform == "cpu":
        lse = jax.jit(lambda q, k, v: fa._xla_select_with_lse(
            q, k, v, keep, scale, 1))(q, k, v)[1]
    else:
        lse = jax.jit(lambda q, k, v: fa._select_fwd_impl(
            q, k, v, keep, scale, 1, False))(q, k, v)[1]
    return (q_index, k_index, weight, q, k, lse, keep), scale


def _dot_precision_probe(d_logits, k_index):
    """What a float32 ``dot`` at the default precision carries on this device
    (``model/indexer.py xla_index_loss``'s two gradient contractions): its
    result against the same contraction of operands rounded to bfloat16
    first, and against ``highest``, each over the largest entry."""
    import jax
    import jax.numpy as jnp

    a = d_logits.astype(jnp.float32)
    b = k_index.astype(jnp.float32)
    default = jax.jit(lambda a, b: jnp.einsum("ns,sd->nd", a, b))(a, b)
    rounded = jax.jit(lambda a, b: jnp.einsum(
        "ns,sd->nd", a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32))(a, b)
    with jax.default_matmul_precision("highest"):
        highest = jax.jit(lambda a, b: jnp.einsum("ns,sd->nd", a, b))(a, b)
    top = float(jnp.max(jnp.abs(highest)))
    return {"default_to_bfloat16_operands":
            float(jnp.max(jnp.abs(default - rounded))) / top,
            "default_to_highest":
            float(jnp.max(jnp.abs(default - highest))) / top}


def _index_loss_leg(s: int = 16384, heads: int = 32, kv_heads: int = 4,
                    f: int = 128, index_heads: int = 16, d: int = 64,
                    topk: int = 2048) -> bool:
    """The index-loss kernel ALONE at the Keye-VL-2.0 cell's shape beside the
    XLA form: each's ms a call by the device trace, and the five outputs of
    both against the XLA form in float32 ``highest``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from homebrewnlp_tpu.model import indexer
    from homebrewnlp_tpu.parallel import index_loss as il

    platform = jax.devices()[0].platform
    interpret = platform == "cpu"
    operands, scale = _index_loss_operands(s, heads, kv_heads, f, index_heads,
                                           d, topk)
    tiles = il.index_loss_tile(s)
    if interpret and tiles is None:
        tiles = (min(64, s), min(128, s))
    names = ("value", "top", "d_q", "d_k", "d_w")
    kernel = jax.jit(lambda *t: il.index_loss_pass(
        *t, scale, tiles=tiles, interpret=interpret))
    xla = jax.jit(lambda *t: indexer.xla_index_loss(*t, scale))
    with jax.default_matmul_precision("highest"):
        want = jax.block_until_ready(jax.jit(
            lambda *t: indexer.xla_index_loss(*t, scale))(*(
                t.astype(jnp.float32) if t.dtype == jnp.bfloat16 else t
                for t in operands)))
    errs = {name: dict(zip(names, (round(e, 7) for e in _errors(
        jax.block_until_ready(fn(*operands)), want).values())))
        for name, fn in (("kernel", kernel), ("xla", xla))}
    ms = {"kernel": _kernel_ms(lambda: kernel(*operands), "index_loss_pass"),
          "xla_module": _module_ms(lambda: xla(*operands)),
          "kernel_module": _module_ms(lambda: kernel(*operands))}
    ok = (platform == "cpu" or il.kernel_applies(s, True)
          ) and all(e <= max(INDEX_LOSS_FLOOR,
                             INDEX_LOSS_ROOM * errs["xla"][name])
                    for name, e in errs["kernel"].items())
    probe = _dot_precision_probe(
        jax.random.normal(jax.random.PRNGKey(7), (512, min(s, 4096))) * 1e-4,
        operands[1][0, :min(s, 4096)])
    print(json.dumps({
        "kernel": "index_loss_pass", "ok": bool(ok),
        "implementation": "pallas (interpret)" if interpret else "pallas",
        "tiles": list(tiles),
        "walked_over_visible_pairs": {
            "kernel": round(il.walked_over_visible(s, tiles), 4),
            "xla": round(indexer.walked_over_visible(s, False), 4)},
        "max_err_over_max_ref": errs,
        "tolerance": f"{INDEX_LOSS_ROOM} x the XLA form's, or "
                     f"{INDEX_LOSS_FLOOR}",
        "ms_a_call": ms, "float32_dot_at_default_precision": probe,
        "shapes": [list(t.shape) for t in operands],
        "dtype": "bfloat16"}), flush=True)
    return bool(ok)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--flash-seq", type=int, default=16384)
    ap.add_argument("--mixer-batch", type=int, default=32)
    ap.add_argument("--solve-chunks", type=int, default=256,
                    help="chunks of the solve's [1, chunks, 10, 64, 64]")
    ap.add_argument("--band-heads", type=int, default=72,
                    help="heads of the band leg's [2, seq, heads, 128]")
    ap.add_argument("--band-seq", type=int, default=8192)
    ap.add_argument("--scan-seq", type=int, default=8192)
    ap.add_argument("--only-scan", action="store_true",
                    help="run the chunked scan's leg alone")
    ap.add_argument("--rule-seq", type=int, default=16384)
    ap.add_argument("--only-rule", action="store_true",
                    help="run the chunked delta rule's leg alone")
    ap.add_argument("--kda-rule-seq", type=int, default=16384)
    ap.add_argument("--only-kda-rule", action="store_true",
                    help="run layer kda's chunked rule's leg alone")
    ap.add_argument("--index-loss-seq", type=int, default=16384)
    ap.add_argument("--only-index-loss", action="store_true",
                    help="run the index-loss kernel's leg alone, at the "
                         "Keye-VL-2.0 cell's shape")
    ap.add_argument("--select-seq", type=int, default=16384)
    ap.add_argument("--only-select", action="store_true",
                    help="run the selected flash kernels' leg alone, both "
                         "forms at their cells' shapes")
    ap.add_argument("--select-tiles", default="",
                    help="with --only-select: also time the library's three "
                         "kernels at these tiles, e.g. 512x512,1024x1024")
    ap.add_argument("--only-flash-forward", action="store_true",
                    help="run the causal flash forward's leg alone, at the "
                         "six shapes its cells hand it")
    ap.add_argument("--flash-forward-seq", type=int, default=0,
                    help="with --only-flash-forward: every shape at this "
                         "many positions and two heads (a CPU rehearsal)")
    ap.add_argument("--only-flash-backward", action="store_true",
                    help="run the flash backward's leg alone: the one-pass "
                         "kernel against the split pair at the six shapes "
                         "its cells hand it")
    ap.add_argument("--flash-backward-seq", type=int, default=0,
                    help="with --only-flash-backward: every shape at this "
                         "many positions and two heads (a CPU rehearsal)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from homebrewnlp_tpu.parallel import flash_attention as flash
    from homebrewnlp_tpu.parallel import map_mixer

    if args.only_index_loss:
        ok = _index_loss_leg(args.index_loss_seq)
        print(json.dumps({"ok": bool(ok)}), flush=True)
        return 0 if ok else 1
    if args.only_flash_forward:
        ok = _flash_forward_leg(args.flash_forward_seq)
        print(json.dumps({"ok": bool(ok)}), flush=True)
        return 0 if ok else 1
    if args.only_flash_backward:
        ok = _flash_backward_leg(args.flash_backward_seq)
        print(json.dumps({"ok": bool(ok)}), flush=True)
        return 0 if ok else 1
    if args.only_select:
        tiles = [tuple(int(n) for n in pair.split("x"))
                 for pair in args.select_tiles.split(",") if pair]
        ok = _select_leg(1, args.select_seq, 32, 4, other_tiles=tiles)
        ok &= _select_leg(64, args.select_seq, 16, 1, other_tiles=tiles)
        print(json.dumps({"ok": bool(ok)}), flush=True)
        return 0 if ok else 1
    if args.only_scan or args.only_rule or args.only_kda_rule:
        ok = _scan_leg(args.scan_seq) if args.only_scan \
            else _rule_leg(args.rule_seq) if args.only_rule \
            else _kda_rule_leg(args.kda_rule_seq)
        print(json.dumps({"ok": bool(ok)}), flush=True)
        return 0 if ok else 1

    def rand(seed, shape, scale=1.0):
        return (scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                          jnp.float32)).astype(jnp.bfloat16)

    ok = True
    s, heads, d = args.flash_seq, 2, 128
    qkv = [rand(i, (1, s, heads, d)) for i in range(3)]
    ref_head = _with_grads(lambda q, k, v: flash._xla_reference(
        q, k, v, d ** -0.5, True))

    def flash_ref(ct, q, k, v):
        # head by head: the dense reference keeps [s, s] float32 scores
        # alive through its backward, and one head's worth is what fits
        per_head = [ref_head(ct[:, :, h:h + 1], q[:, :, h:h + 1],
                             k[:, :, h:h + 1], v[:, :, h:h + 1])
                    for h in range(heads)]
        return [jnp.concatenate(parts, axis=2) for parts in zip(*per_head)]

    ok &= _run("flash_attention", lambda q, k, v: flash.attention(
        q, k, v, causal=True), flash_ref, qkv, cotangent_seed=7)

    h, seq, f, b = 8, 512, 512, args.mixer_batch
    bias = rand(11, (h, seq, seq), 0.1)
    val = rand(12, (b, seq, h, f))
    mixer_ref = _with_grads(lambda bias_, v_: map_mixer._xla_reference(
        bias_, v_, True))
    ok &= _run("map_mixer", lambda bias_, v_: map_mixer.mix(
        bias_, v_, causal=True), mixer_ref, [bias, val], cotangent_seed=13)

    ok &= _solve_leg((1, args.solve_chunks, 10, 64, 64))

    ok &= _band_leg((2, args.band_seq, args.band_heads, 128))

    ok &= _scan_leg(args.scan_seq)

    ok &= _rule_leg(args.rule_seq)

    ok &= _kda_rule_leg(args.kda_rule_seq)

    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
