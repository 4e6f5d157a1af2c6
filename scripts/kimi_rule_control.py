#!/usr/bin/env python3
"""A second control behind the Kimi-Linear cell's ``logit_tolerance``: the
PROGRAM with what its delta rule keeps in float32 rounded to bfloat16.

    python scripts/kimi_rule_control.py --workload train_kimi_linear_ep32_s16k --seed <n>

``benchmark/precision_control.py`` lowers the reference's residual stream,
which says nothing of layer ``kda``'s own float32 parts: the log-decays'
cumulative sums, the solve's input and the state carried over the chunks
(``model/kda.py KEPT``).  This sets ``KEPT`` to bfloat16 and runs that file's
``main`` as it stands — the same set-up, the driver's own comparison against
the float32 reference at the cell's limit — so the ``program`` entry of the
JSON line it prints is the program one precision below the one the
configuration states.  The float8 stream is kept beside it for scale.  Exit 0
where the limit refuses that program, 1 where it lets it pass.

``--rule-alone`` leaves the model out: 8 of the cell's heads (128 / 128 over
16,384 positions, the layer's own ranges of ``beta`` and of the log-decay a
channel, bfloat16 ``q, k, v``) through the rule as the layer runs it there —
the Pallas pairs of parallel/kda_rule.py where ``kda_kernel_applies`` (a
TPU), else the XLA form — and with ``KEPT`` at bfloat16, each against the
reference's recurrence position by position in float32 ``highest`` on the
same device: what the logits' limit cannot tell apart, the rule's own output
does.  One JSON line that names the form, exit 0.

``--rehearse-cpu`` runs the same path at the cell's toy size on the CPU
(exit 10).
"""
import contextlib
import io
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


class _Tee(io.StringIO):
    def write(self, text):
        sys.__stdout__.write(text)
        sys.__stdout__.flush()
        return super().write(text)


def rule_alone(s: int, heads: int = 8, dk: int = 128, dv: int = 128) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.reference.kimi_linear_48b_a3b import recurrence
    from homebrewnlp_tpu.model import kda
    rng = np.random.default_rng(58)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    # the layer's ranges at its seeded start: unit keys and queries, beta =
    # sigmoid(.), g = -A softplus(.) with A = U(1, 16) a head and softplus(.)
    # log-uniform in [1e-3, 1e-1] a channel
    q, k, v = (jnp.asarray(t, jnp.bfloat16) for t in (
        unit(rng.normal(size=(1, s, heads, dk))) * dk ** -0.5,
        unit(rng.normal(size=(1, s, heads, dk))),
        rng.normal(size=(1, s, heads, dv))))
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (1, s, heads)), jnp.float32)
    g = jnp.asarray(-rng.uniform(1.0, 16.0, (heads, 1)) * np.exp(rng.uniform(
        np.log(1e-3), np.log(1e-1), (1, s, heads, dk))), jnp.float32)
    # the reference starts from the normalised q and k as the rule rounds
    # them: what is compared is the rule's float32 parts, not the norms
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(recurrence)(*(t.astype(jnp.float32) for t in (
            kda.unit(q, dk ** -0.5), kda.unit(k, 1.0), v)), beta, g))
    errors = {}
    chunk = min(kda.CHUNK, s)
    kernels = kda.kda_kernel_applies(chunk, heads, dk, dv, s)
    rule = kda.kernel_rule if kernels else kda.normalised(kda.kda_rule)
    for kept in (jnp.float32, jnp.bfloat16):
        kda.KEPT = kept
        got = np.asarray(jax.jit(lambda *a: rule(
            *a, chunk)[0].astype(jnp.float32))(q, k, v, beta, g))
        errors[jnp.dtype(kept).name] = float(
            np.max(np.abs(got - want)) / np.max(np.abs(want)))
    print(json.dumps({"rule_alone": [1, s, heads, dk, dv],
                      "device": jax.devices()[0].device_kind,
                      "rule": "pallas" if kernels else "xla",
                      "max_err_over_max_recurrence_by_kept": errors}),
          flush=True)
    return 0


def main(argv=None) -> int:
    from benchmark import precision_control
    argv = list(sys.argv[1:] if argv is None else argv)
    rehearsal = "--rehearse-cpu" in argv
    if rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if "--rule-alone" in argv:
        return rule_alone(256 if rehearsal else 16384)
    import jax.numpy as jnp

    from homebrewnlp_tpu.model import kda
    kda.KEPT = jnp.bfloat16
    precision_control.STREAMS = ("float8_e4m3fn",)
    print(f"layer kda keeps its cumulative log-decays, the solve's input and "
          f"the carried state in {jnp.dtype(kda.KEPT).name}", flush=True)
    said = _Tee()
    with contextlib.redirect_stdout(said):
        code = precision_control.main(argv)
    if code not in (0, precision_control.EXIT_NOT_SEPARATED):
        return code
    out = json.loads(said.getvalue().strip().splitlines()[-1])
    refused = not out["program"]["logits_agree"]
    print(json.dumps({"rule_kept_in": "bfloat16", "refused": refused,
                      "logit_error": out["program"]["logit_error"],
                      "logit_tolerance": out["logit_tolerance"]}), flush=True)
    return 0 if refused else 1


if __name__ == "__main__":
    sys.exit(main())
