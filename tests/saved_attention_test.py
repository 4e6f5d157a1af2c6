"""The attention kind under the ``checkpoint`` strategy (ISSUE 40): every
flash layer's ``(out, lse)`` rides the block's ``jax.checkpoint`` as named
values, so the block's replay runs no forward attention computation — the
backward is the flash-2 pass on the replayed ``q, k, v`` and the SAVED
``(out, lse)`` (``flash_precomputed``).  At toy size, on the CPU, through the
standard attention with grouped queries, a windowed layer, layer ``cca`` and
the generic dot-product attention; where nothing names the kind, the step is
the parent's."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model, remat
from homebrewnlp_tpu.model.blocks import _checkpoint_policy, _name_chan
from homebrewnlp_tpu.model.basic import MLP_SAVED_NAMES
from homebrewnlp_tpu.parallel import flash_attention as flash_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GROUPED = "attention-rope-qk_norm-q_heads6-kv_heads2"
WINDOWED = "attention-rope-q_heads4-kv_heads2-gate-window32"
CCA = "cca-q_heads4-kv_heads2-rotary_pct50-theta5000000"
GENERIC = "attention-dot_product-embedded-absolute"


def _block(*layers):
    return {"skip": True, "layer": list(layers)}


#: a stream of 4 x 16, two sequences of 128 positions (one whole tile of the
#: flash route), the three callers of ``_flash`` and the generic attention's
#: ``_maybe_flash_attention`` in one period, an MLP between them
TINY = {"depth": 2, "heads": 4, "features_per_head": 16,
        "sequence_length": 128, "train_batch_size": 2, "vocab_size": 272,
        "tpu_size": 1, "use_checkpointing": False,
        "memory_reduction_strategy": "checkpoint",
        "block_config": [_block("norm-rms-scale", GROUPED),
                         _block("norm-rms-scale", "mlp-silu"),
                         _block("norm-rms-scale", WINDOWED),
                         _block("norm-rms-scale", CCA),
                         _block("norm-rms-scale", GENERIC)]}
#: query heads of the four calls, in execution order
HEADS = (6, 4, 4, 4)


def _build(policy: str, **extra):
    with open(os.path.join(REPO, "configs", "olmoe_1b_7b.json")) as f:
        config = {**json.load(f), **TINY, "calculation_dtype": "float32",
                  "remat_policy": policy, **extra}
    params = ModelParameter(config)
    assert not params.unknown_config_keys
    model = Model(params)
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, 256, (2, config["sequence_length"], 1)).astype(
        np.int32)
    batch = {"token_x": tokens, "token_y": np.roll(tokens, -1, axis=1)}
    variables = {k: jnp.asarray(v)
                 for k, v in model.init(batch, seed=13).items()}
    step = jax.value_and_grad(lambda v: model.apply(v, batch).total_loss.data)
    return params, model, variables, step


def _walk(jaxpr, visit, inside=""):
    """``visit(eqn, inside)`` on every equation, through every nested jaxpr;
    ``inside`` names the primitives whose jaxprs hold it."""
    for eqn in jaxpr.eqns:
        visit(eqn, inside)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _walk(sub, visit, f"{inside}/{eqn.primitive.name}")


def _attention_programs(step, variables):
    """What the gradient's jaxpr holds of attention: ``forwards`` — softmax
    forwards (one ``exp`` over a ``[.., s, s]`` score map each: the dense
    form's, and the one ``_xla_reference_with_lse`` runs where the kernel
    would) — ``backwards`` — flash-2 backward kernels — and the names the
    values bear."""
    found = {"forwards": 0, "backwards": 0, "names": []}

    def visit(eqn, inside):
        if "pallas_call" in inside:
            return      # a backward kernel's own body makes p again, a tile
        if eqn.primitive.name == "exp" \
                and eqn.outvars[0].aval.shape[-2:] == (128, 128):
            found["forwards"] += 1
        elif eqn.primitive.name == "pallas_call":
            found["backwards"] += eqn.params["name"].startswith("flash_bwd")
        elif eqn.primitive.name == "name" \
                and eqn.params["name"] not in MLP_SAVED_NAMES:
            found["names"].append(eqn.params["name"])

    _walk(jax.make_jaxpr(step)(variables).jaxpr, visit)
    return found


@pytest.mark.parametrize("scan", [False, True], ids=["unrolled", "scan"])
def replay_runs_no_forward_attention_test(scan):
    """``"stash"`` (the toy's 128 keys are under the rule's 2,048, so the
    configuration names the kind itself) against ``"recompute"``: the loss is
    equal, every gradient within float32 rounding of the dense form's
    autodiff, the four calls of a period run ONE softmax forward each where
    they ran two (the step's and the replay's), the backward is the flash-2
    pass, and the saved residuals of each block's ``jax.checkpoint`` hold the
    two names."""
    results = {}
    for policy in ("recompute", "stash"):
        params, model, variables, step = _build(policy, scan_layers=scan)
        results[policy] = (params, _attention_programs(step, variables),
                           jax.jit(step)(variables))
    off, before, (want_loss, want) = results["recompute"]
    on, after, (loss, grads) = results["stash"]
    # a scanned depth is one jaxpr
    calls = len(HEADS) * (1 if scan else TINY["depth"])
    assert before == {"forwards": 2 * calls, "backwards": 0,
                      "names": []}
    assert after["forwards"] == calls and after["backwards"] == calls
    assert sorted(after["names"]) == sorted(flash_mod.SAVED_NAMES * calls)
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    assert set(grads) == set(want)
    for name in want:
        np.testing.assert_allclose(np.asarray(grads[name]),
                                   np.asarray(want[name]), rtol=2e-4,
                                   atol=2e-6, err_msg=name)
    # what rides, and what the gauges read: out [2, 128, heads, 16] and lse
    # [2 x heads, 128] in float32, a call
    saved = sum(HEADS) * 2 * 128 * (16 * 4 + 4) * TINY["depth"]
    assert remat.stash_plan(on)["attention"] == (len(HEADS) * 2, saved)
    assert remat.stash_names(on) == flash_mod.SAVED_NAMES
    assert remat.saved_attention_keys(on) == 0
    assert _name_chan(on, None) == {"mode": "name", "min_keys": 0,
                                    "kinds": frozenset({"attention"})}
    assert remat.stash_plan(off)["attention"] == (0, 0)
    assert remat.stash_names(off) == () and _name_chan(off, None) is None
    assert _checkpoint_policy(off) is jax.checkpoint_policies.nothing_saveable


def saved_residuals_hold_the_two_names_test():
    """One block's region, as ``jax.checkpoint`` partitions it under the
    step's own policy: the residuals it keeps for the backward beside its
    arguments are exactly the call's ``out`` and ``lse``."""
    from jax._src.ad_checkpoint import saved_residuals
    q, k, v = (jnp.asarray(np.random.default_rng(i).normal(
        size=(2, 128, 4, 16)).astype(np.float32)) for i in range(3))
    params, _, _, _ = _build("stash")
    chan = _name_chan(params, None)

    def region(q, k, v):
        return jnp.sum(jnp.sin(flash_mod.attention(
            q * 1.5, k, v, causal=True, stash=chan)))

    saved = saved_residuals(
        jax.checkpoint(region, policy=_checkpoint_policy(params)), q, k, v)
    kept = sorted((aval.shape, why) for aval, why in saved
                  if "argument" not in why)
    assert [shape for shape, _ in kept] == [(2, 128, 4, 16), (8, 128)]
    # ``out`` is read again after its name (the layer's output), so
    # jax.checkpoint rounds the residual to its own precision behind the
    # name's equation and reports that no-op as the producer
    (_, why_out), (_, why_lse) = kept
    assert "named 'flash_lse'" in why_lse
    assert "named 'flash_out'" in why_out or (
        "reduce_precision" in why_out and "flash_attention.py" in why_out)
    # the same region under the policy of a step that names nothing keeps
    # none: the names are free
    off, _, _, _ = _build("recompute")
    saved = saved_residuals(
        jax.checkpoint(region, policy=_checkpoint_policy(off)), q, k, v)
    assert all("argument" in why for _, why in saved)


@pytest.mark.parametrize("case", ["auto_under_2048_keys", "recompute",
                                  "strategy_none", "flash_off"])
def step_that_names_nothing_is_the_parents_test(case):
    """Where the kind does not ride, no block gets a channel and the step's
    jaxpr is the one ``"recompute"`` traces, text for text: ``"auto"`` at the
    toy's 128 keys, strategy ``none`` and a model without the flash route
    even under an explicit ``"stash"``."""
    # (without the period's MLP: layer ``mlp``'s gate and up are a kind of
    # their own, PR 52, which the toy's bytes fit and a "stash" names)
    no_mlp = [b for b in TINY["block_config"] if "mlp-silu" not in b["layer"]]
    extra = {"auto_under_2048_keys": {"block_config": no_mlp},
             "recompute": {},
             "strategy_none": {"memory_reduction_strategy": "none"},
             "flash_off": {"use_flash_attention": False,
                           "block_config": no_mlp}}[case]
    policy = {"auto_under_2048_keys": "auto",
              "recompute": "recompute"}.get(case, "stash")
    texts = []
    for p in (policy, "recompute"):
        params, _, variables, step = _build(p, **extra)
        assert _name_chan(params, None) is None
        assert remat.stash_plan(params)["attention"] == (0, 0)
        assert not set(flash_mod.SAVED_NAMES) & set(remat.stash_names(params))
        texts.append(str(jax.make_jaxpr(step)(variables)))
    assert texts[0] == texts[1]


def windowed_layer_under_the_rule_keeps_the_plain_call_test():
    """A channel whose ``min_keys`` a windowed call does not reach leaves
    that call alone — the plain dense form here, the plain kernel on a TPU —
    and names the whole-triangle call beside it."""
    q, k, v = (jnp.asarray(np.random.default_rng(i).normal(
        size=(1, 256, 2, 16)).astype(np.float32)) for i in range(3))
    chan = {"mode": "name", "kinds": frozenset({"attention"}),
            "min_keys": 256}

    def names(window):
        jaxpr = jax.make_jaxpr(jax.grad(lambda q: jnp.sum(flash_mod.attention(
            q, k, v, causal=True, stash=chan, window=window))))(q)
        found = []
        _walk(jaxpr.jaxpr, lambda eqn, _: found.append(eqn.params["name"])
              if eqn.primitive.name == "name" else None)
        return tuple(found)

    assert names(128) == ()
    assert names(None) == names(256) == names(512) == flash_mod.SAVED_NAMES
