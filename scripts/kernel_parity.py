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

Shapes: flash at the long-context recipe's per-chip shape (seq 16,384, head
dim 128; two heads so the dense reference's [s, s] scores fit beside it);
the mixer at the flagship's (8 heads, seq 512, 512 features/head, batch 32).
Operands are bfloat16, the dtype both recipes compute in.
"""
import argparse
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


def _errors(got, want):
    """{name: max|got - want| / max|want|} over matching tuples of arrays."""
    import numpy as np
    out = {}
    for name, g, w in zip(("out", "d0", "d1", "d2"), got, want):
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--flash-seq", type=int, default=16384)
    ap.add_argument("--mixer-batch", type=int, default=32)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from homebrewnlp_tpu.parallel import flash_attention as flash
    from homebrewnlp_tpu.parallel import map_mixer

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

    print(json.dumps({"ok": bool(ok)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
