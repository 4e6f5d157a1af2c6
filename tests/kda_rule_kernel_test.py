"""parallel/kda_rule.py: the Pallas pairs of layer ``kda``'s chunked rule — a
delta rule with a log-decay a CHANNEL of the key — in interpret mode on the
CPU against the XLA form ``model/kda.py kda_rule`` behind the layer's norms
(``normalised``) and autodiff's gradients of it, against the recurrence run
position by position in float32, against ``gated_delta``'s rule where the
decay is flat; what the scores' kernel hands on to the walk (the norms, the
running sum of the log-decays); that no ``exp`` in a kernel
body sees a positive operand; the predicate that chooses between the two
forms, the layer with and without the kernels, what a step lowered for a TPU
carries, and the start-up fact."""
import functools
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import gated_delta as delta_mod
from homebrewnlp_tpu.model import kda as kda_mod
from homebrewnlp_tpu.model import recurrent
from homebrewnlp_tpu.parallel import kda_rule as kr

import harness
from kimi_linear_test import _block, _build

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
def _recurrence():
    return harness.reference("kimi_linear_48b_a3b").recurrence


@functools.lru_cache(maxsize=None)
def recurrence_rule():
    """The recurrence behind the layer's norms, as a rule: what the pairs and
    bfloat16 operands (``kernel_steps_test.py``) are held against."""
    recurrence = _recurrence()
    return kda_mod.normalised(lambda *args: (recurrence(*args[:5]),))


def _inputs(s, heads, low, dk=16, dv=16, dtype=jnp.float32, seed=0,
            batch=1):
    """Keys that share a direction (the triangular system is then far from
    the identity) and, like the queries, are of every length between a half
    and two (the rule normalises them itself), ``beta`` over all of ``(0,
    1)`` and at its top every fifth position, a log-decay a channel between
    0 and ``low`` a position."""
    rng = np.random.default_rng(seed)
    shared = rng.normal(size=(batch, 1, heads, dk))

    def some_length(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True) \
            * rng.uniform(0.5, 2.0, size=x.shape[:-1] + (1,))

    q = some_length(rng.normal(size=(batch, s, heads, dk)) + shared)
    k = some_length(rng.normal(size=(batch, s, heads, dk)) + 2 * shared)
    v = rng.normal(size=(batch, s, heads, dv))
    beta = rng.uniform(0.0, 1.0, size=(batch, s, heads))
    beta[:, ::5] = 1.0
    g = low * rng.uniform(0.0, 1.0, size=(batch, s, heads, dk))
    weights = jnp.asarray(rng.normal(size=v.shape), jnp.float32)
    return (*(jnp.asarray(t, dtype) for t in (q, k, v)),
            *(jnp.asarray(t, jnp.float32) for t in (beta, g))), weights


def steer_interpreted(monkeypatch, heads_a_block=None):
    """``kernel_rule`` with both pairs interpreted, a head block of the
    caller's."""
    harness.steer_interpreted(monkeypatch, kda_mod, kr, "kda_rule_pair", "kda_scores",
                              heads_a_block=heads_a_block)


@pytest.fixture
def interpreted(monkeypatch):
    return functools.partial(steer_interpreted, monkeypatch)


_value_and_grads = harness.rule_value_and_grads
_close = functools.partial(harness.assert_close_each,
                           names="o dq dk dv dbeta dg".split())


# (sequence, chunk, heads, heads a block, the steepest log-decay a position):
# the cell's chunk with a last block of one head of three over two lane
# tiles (the state crosses a tile); three tiles and a last block of two of
# five; eight chunks a lane tile (no sub-chunk before another); four; one
# chunk a tile (seven sub-chunks before the last); a step's log-decay of -20
# (exp(-gamma) overflows float32 after five positions: no inf, no nan);
# almost none
@pytest.mark.parametrize("s,chunk,heads,block,low", [
    (256, 64, 3, 2, -1.0), (384, 64, 5, 3, -0.3), (256, 16, 3, 3, -1.0),
    (128, 32, 4, 4, -1.0), (256, 128, 2, 1, -0.2), (256, 64, 2, 1, -20.0),
    (128, 64, 3, 2, -1e-4)],
    ids=["c64_h3", "c64_h5_three_tiles", "c16", "c32", "c128", "c64_steep",
         "c64_slow"])
def pairs_match_the_xla_form_test(interpreted, s, chunk, heads, block, low):
    """Output, ``max|T|``, the log-decay watch and all five gradients
    against autodiff through the XLA form over all heads at once."""
    inputs, weights = _inputs(s, heads, low)
    interpreted(block)
    got, statistics = _value_and_grads(kda_mod.kernel_rule, inputs, weights,
                                       chunk)
    want, want_statistics = _value_and_grads(
        kda_mod.normalised(kda_mod.kda_rule), inputs, weights, chunk)
    np.testing.assert_allclose(statistics, want_statistics, rtol=1e-6)
    # at -20 a step gamma reaches -1,280, where float32 is spaced 1.2e-4:
    # the kernel's running sum (doubling steps) and XLA's round it apart
    _close(got, want, 1e-4 if low == -20.0 else 2e-5)


@pytest.mark.parametrize("chunk,s,low", [(16, 128, -1.0), (64, 256, -1.0),
                                         (64, 128, -20.0), (128, 256, -0.2)],
                         ids=["c16", "c64_two_tiles", "c64_steep", "c128"])
def pairs_are_the_recurrence_test(interpreted, chunk, s, low):
    """``o`` and all five gradients against the recurrence of the module
    docstring run position by position in float32 (``lax.scan``'s own
    reverse mode)."""
    inputs, weights = _inputs(s, 3, low, dk=16, dv=16)
    interpreted(2)
    got, (_, log_decay_min) = _value_and_grads(kda_mod.kernel_rule, inputs,
                                               weights, chunk)
    recurrence = _recurrence()
    want, _ = _value_and_grads(kda_mod.normalised(
        lambda *args: (recurrence(*args[:5]),)), inputs, weights, chunk)
    _close(got, want, 1e-4)
    if low == -20.0:
        assert float(log_decay_min) < -88 * 2        # exp(-gamma) = inf


@pytest.mark.parametrize("chunk,kept", [(64, jnp.float32), (16, jnp.float32),
                                        (128, jnp.float32),
                                        (64, jnp.bfloat16)],
                         ids=["c64", "c16", "c128", "c64_kept_bfloat16"])
def scores_hand_on_the_norms_and_the_running_sum_test(chunk, kept):
    """``kda_scores``' last three outputs are XLA's: ``gamma`` the cumulative
    sum of ``g`` along each chunk (through ``kept``: at bfloat16 a value
    bfloat16 holds, near the float32 sum), ``q`` and ``k`` over their norms
    as ``model/kda.py unit`` rounds them."""
    (q, k, _, _, g), _ = _inputs(256, 3, -1.0, dtype=jnp.bfloat16)
    bsz, s, h, dk = q.shape
    _, _, gamma, q_unit, k_unit = kr.kda_scores(
        kr.sequence_minor(q), kr.sequence_minor(k), kr.sequence_minor(g), h,
        chunk, min(chunk, 16), dk ** -0.5, delta_mod.L2_EPS, kept, 2, True)
    want = jnp.cumsum(g.reshape(bsz, s // chunk, chunk, h, dk), axis=2)
    got = kr.positions_major(gamma, g.shape).reshape(want.shape)
    if kept == jnp.float32:
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=1e-6)
    else:
        assert bool(jnp.all(got.astype(kept).astype(jnp.float32) == got))
        np.testing.assert_allclose(got, want, rtol=0.05, atol=0.05)
    for got, raw, scale in ((q_unit, q, dk ** -0.5), (k_unit, k, 1.0)):
        np.testing.assert_allclose(
            np.asarray(kr.positions_major(got, raw.shape), np.float32),
            np.asarray(kda_mod.unit(raw, scale), np.float32),
            rtol=2.0 ** -7, atol=1e-6)


def a_flat_decay_is_gated_deltas_rule_test(interpreted):
    """With ``g`` equal over a head's channels the kernels' rule is
    ``gated_delta``'s XLA rule (its state transposed): outputs and
    gradients."""
    (q, k, v, beta, g), weights = _inputs(128, 3, -0.5, dv=32)
    flat = g[..., 0]
    interpreted(2)

    def ours(q, k, v, beta, flat, chunk):
        return kda_mod.kernel_rule(
            q, k, v, beta, jnp.broadcast_to(flat[..., None], g.shape), chunk)

    got, _ = _value_and_grads(ours, (q, k, v, beta, flat), weights, 64)
    want, _ = _value_and_grads(kda_mod.normalised(delta_mod.delta_rule),
                               (q, k, v, beta, flat), weights, 64)
    _close(got, want, 2e-5)


def what_the_kernels_keep_in_float32_is_felt_test(interpreted, monkeypatch):
    """``KEPT`` reaches the kernels' path too: at bfloat16 the cumulative
    log-decays, the solve's input and the state the walk carries are rounded
    and the rule moves away from the recurrence by orders of magnitude."""
    (q, k, v, beta, g), _ = _inputs(256, 3, -0.3)
    want = kda_mod.normalised(_recurrence())(q, k, v, beta, g)
    interpreted(2)

    def off():
        got = kda_mod.kernel_rule(q, k, v, beta, g, 64)[0]
        return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))

    kept = off()
    monkeypatch.setattr(kda_mod, "KEPT", jnp.bfloat16)
    assert kept < 1e-5 and off() > 100 * kept


@pytest.mark.parametrize("rule", ["kernels", "xla"])
def no_exp_of_a_positive_decay_difference_is_formed_test(
        rule, interpreted, monkeypatch):
    """Every ``exp`` of the rule, forward and backward, is given values <= 0
    (or -inf) on log-decays of -20 a step: the jaxpr is walked with every
    ``exp``'s operand recorded, INTO the ``pallas_call`` bodies (their own
    ``exp``s report through a callback while the call is interpreted)."""
    inside = []
    if rule == "kernels":
        class Spied:
            """``jax.numpy`` as the kernels' module sees it, ``exp``
            reporting its operand's largest entry."""
            def __getattr__(self, name):
                return getattr(jnp, name)

            @staticmethod
            def exp(x):
                jax.debug.callback(lambda top: inside.append(float(top)),
                                   jnp.max(x))
                return jnp.exp(x)

        monkeypatch.setattr(kr, "jnp", Spied())
        jax.clear_caches()
        interpreted(1)
    (q, k, v, beta, g), _ = _inputs(128, 2, -20.0)
    fn = kda_mod.kernel_rule if rule == "kernels" \
        else kda_mod.normalised(kda_mod.kda_rule)
    closed = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(fn(*a, 32)[0]), argnums=(0, 1, 2, 3, 4)))(
        q, k, v, beta, g)

    out, seen = harness.exp_operands(closed, [q, k, v, beta, g],
                                     ("exp", "pallas_call"))
    jax.effects_barrier()
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in out)
    if rule == "kernels":
        # four kernels, each with the rows', the columns' and the diagonal
        # blocks' or the walk's three decays
        assert len(inside) >= 40 and max(inside) <= 0.0
        jax.clear_caches()
    else:
        assert len(seen) >= 8
    assert max(seen, default=0.0) <= 0.0


@pytest.mark.parametrize(
    "chunk,heads,d_k,d_v,sequence,backend,takes", [
        (64, 32, 128, 128, 16384, "tpu", True),    # the published widths
        (16, 1, 16, 16, 128, "tpu", True),         # one tile, one head
        (128, 4, 128, 64, 256, "tpu", True),
        (64, 32, 128, 128, 16384, "cpu", False),
        (64, 32, 128, 128, 16384, "gpu", False),
        (48, 32, 128, 128, 16320, "tpu", False),   # no chunk the solve takes
        (256, 32, 128, 128, 16384, "tpu", False),  # beyond a lane tile
        (8, 32, 128, 128, 16384, "tpu", False),
        (64, 32, 128, 128, 16384 + 64, "tpu", False),   # no whole lane tiles
        (64, 3, 16, 8, 128, "tpu", False),         # the toy value width
        (64, 3, 24, 16, 128, "tpu", False),        # an odd key width
        (64, 0, 128, 128, 16384, "tpu", False),
        (64, 512, 128, 128, 16384, "tpu", False)])  # a state beyond VMEM
def predicate_test(chunk, heads, d_k, d_v, sequence, backend, takes):
    assert kr.kda_kernel_applies(chunk, heads, d_k, d_v, sequence,
                                 backend) is takes


def predicate_reads_the_backend_test():
    assert jax.default_backend() == "cpu"
    assert not kr.kda_kernel_applies(64, 32, 128, 128, 16384)


_WIDE = {"kda_key_features": 16, "kda_value_features": 16,
         "sequence_length": 256, "train_batch_size": 1,
         "block_config": [_block("kda")]}


def _as_a_tpu_process(monkeypatch):
    monkeypatch.setattr(kda_mod, "kda_kernel_applies", functools.partial(
        kr.kda_kernel_applies, backend="tpu"))


@pytest.mark.parametrize("extra", [
    {}, {"kda_key_features": 24}, {"sequence_length": 64},
    {"sequence_length": 192}],
    ids=["value_width_8", "key_width_24", "half_a_lane_tile",
         "a_tile_and_a_half"])
def declining_layer_traces_the_xla_form_test(monkeypatch, extra):
    """Widths in no whole sublane tiles, a sequence of no whole lane tiles:
    with the backend steered to the TPU the layer still traces
    ``grouped_rule``'s ops, and no Pallas call."""
    _, params, model, batch, variables = _build(
        "bfloat16", block_config=[_block("kda")], **extra)
    assert recurrent.rule_kernel_layers(params, "tpu") == 0
    plain = harness.step_jaxpr(model, variables, batch)
    _as_a_tpu_process(monkeypatch)
    assert harness.step_jaxpr(model, variables, batch) == plain
    assert "kda_rule_fwd" not in plain and "pallas_call" not in plain


@pytest.mark.parametrize("policy,forwards", [("recompute", 2), ("auto", 1)])
def step_lowered_for_a_tpu_carries_the_kernels_test(monkeypatch, policy,
                                                    forwards):
    """The toy step at kernel shapes lowered for a TPU (no chip, no
    compile): four kinds of ``tpu_custom_call`` — each pair's backward once,
    its forward twice, the step's and the block's replay, where nothing is
    kept (``"recompute"``) and once where the execution's interior rides the
    block's ``jax.checkpoint`` (PR 61: the toy's bytes fit under ``"auto"``)
    — every one under scope ``body/kda/rule``, which
    ``kimi_kda_rule_roofline`` and ``scope_kda_time_share`` read, and no loop
    left there (the XLA form has the groups' ``lax.map`` and a ``while`` of a
    trip a chunk)."""
    _, _, model, batch, variables = _build("bfloat16", remat_policy=policy,
                                           **_WIDE)

    def lowered():
        return jax.jit(jax.grad(
            lambda v, b: model.apply(v, b).total_loss.data)).trace(
            variables, batch).lower(lowering_platforms=("tpu",)).as_text(
            debug_info=True)

    def loops(text):
        return [line for line in text.split("\n") if "stablehlo.while" in line]

    assert "tpu_custom_call" not in lowered()
    xla_loops = loops(lowered())
    _as_a_tpu_process(monkeypatch)
    text = lowered()
    named = dict(re.findall(r'(#loc\d+) = loc\("([^"]+)"', text))

    def paths(line):
        return [named.get(ref, "")
                for ref in re.findall(r"loc\((#loc\d+)\)", line)]

    # a kernel's ``jax.jit`` is a function of the module, called from the
    # layer's scope: the compiled op's name is the two joined
    sites = [(re.search(r"call @_(\w*?(?:fwd|bwd))_impl", line), line)
             for line in text.split("\n")]
    sites = [(found.group(1), paths(line)[0]) for found, line in sites
             if found]
    assert sorted(name for name, _ in sites) == sorted(
        ["bwd", "scores_bwd"] + ["fwd", "scores_fwd"] * forwards)
    for _, path in sites:
        assert scope_key(path + "/kda_rule_fwd/pallas_call") \
            == "body/kda/rule", path
    calls = [paths(line)[0].split("/")[0] for line in text.split("\n")
             if "stablehlo.custom_call @tpu_custom_call" in line]
    assert sorted(set(calls)) == ["kda_rule_bwd", "kda_rule_fwd",
                                  "kda_scores_bwd", "kda_scores_fwd"]
    assert len(xla_loops) >= 2 and len(loops(text)) < len(xla_loops)
    assert not any("kda_0/rule" in path for line in loops(text)
                   for path in paths(line))


#: two ``kda`` blocks of 8 heads at chunk 16: 128 systems, a tile of the
#: solve's kernel
_TWO_BLOCKS = {"kda_heads": 8, "kda_key_features": 16,
               "kda_value_features": 16, "sequence_length": 256,
               "train_batch_size": 1,
               "block_config": [_block("kda"), _block("kda")]}
_FORWARDS = ("kda_scores_fwd", "delta_solve_fwd", "kda_rule_fwd")
_BACKWARDS = ("kda_scores_bwd", "delta_solve_bwd", "kda_rule_bwd")
_REPLAYED = {}


def _two_blocks(monkeypatch, policy: str, admit=None):
    """The toy of two ``kda`` blocks in float32 as a TPU process traces it,
    all three pairs interpreted, under ``policy``; ``admit``: the executions
    the chip's limit admits.  ``(the plan's recurrent kind, the kernels named
    in every region's backward in execution order, (loss, gradients))``."""
    from homebrewnlp_tpu.model import remat
    from homebrewnlp_tpu.parallel import delta_solve as ds
    from homebrewnlp_tpu.utils import flops
    monkeypatch.setattr(kda_mod, "CHUNK", 16)
    _as_a_tpu_process(monkeypatch)
    steer_interpreted(monkeypatch)
    harness.steer(monkeypatch, delta_mod,
                  solve_kernel_applies=functools.partial(
                      ds.solve_kernel_applies, backend="tpu"))
    harness.steer_interpreted(monkeypatch, delta_mod, ds,
                              "inverse_unit_lower", "inverse_unit_lower_bwd")
    _, params, model, batch, variables = _build(
        "float32", remat_policy=policy, **_TWO_BLOCKS)
    offer = kda_mod.kda.declares.offer(params, set())
    unit = (offer.nbytes, offer.interior_nbytes)
    if admit is not None:
        # both outputs and ``admit`` and a half interiors
        monkeypatch.setattr(flops, "hbm_capacity", lambda device=None: (
            int((2 * unit[0] + (admit + 0.5) * unit[1])
                / remat.STASH_HBM_FRACTION), "test"))
    fn = jax.value_and_grad(lambda v: harness.loss_of(model)(v, batch))
    regions = [str(eqn.params["jaxpr"])
               for eqn in jax.make_jaxpr(fn)(variables).jaxpr.eqns
               if eqn.primitive.name == "remat2"][::-1]
    return (remat.stash_plan(params)["recurrent"], unit,
            [{name: text.count(name) for name in _FORWARDS + _BACKWARDS}
             for text in regions], jax.jit(fn)(variables))


@pytest.mark.optimised
@pytest.mark.parametrize("admit", [0, 1, 2])
def saved_interior_is_the_replayed_one_test(monkeypatch, admit):
    """PR 61: a ``kda`` block whose interior is admitted saves what the
    three pairs' forwards hand their backwards, and its replay — the
    ``jax.checkpoint`` region's backward — names no forward call of the
    scores, the solve or the walk; one whose output alone rides names all
    three; admitted are the LAST executions.  Under ``jax.jit`` the loss and
    every gradient are the replayed ones' (``"recompute"``) bit for bit: a
    saved value is the bits its replay would have made."""
    if not _REPLAYED:
        with pytest.MonkeyPatch.context() as patch:
            _REPLAYED["all"] = _two_blocks(patch, "recompute")
    plan, _, regions, (want_loss, want) = _REPLAYED["all"]
    assert plan == (0, 0)
    assert regions == [dict.fromkeys(_FORWARDS + _BACKWARDS, 1)] * 2
    plan, unit, regions, (loss, grads) = _two_blocks(monkeypatch, "auto",
                                                     admit)
    # o [1, 256, 8, 16]; q~, k~ and gamma the same, A, A' and the inverse
    # [1, 16, 8, 16, 16], the states [1, 16, 8, 16, 16], all float32 here
    assert unit == (4 * 256 * 8 * 16,
                    4 * (3 * 256 * 8 * 16 + 4 * 16 * 8 * 16 * 16))
    assert plan == (2, 2 * unit[0] + admit * unit[1])
    for region, names in enumerate(regions):
        kept = region >= 2 - admit
        assert names == {**dict.fromkeys(_FORWARDS, 0 if kept else 1),
                         **dict.fromkeys(_BACKWARDS, 1)}, region
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    assert set(grads) == set(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(grads[name]),
                                      np.asarray(want[name]), err_msg=name)


def rule_fact_counts_the_layers_test(monkeypatch):
    """``hbnlp_delta_rule_kernel_layers``: layer ``kda`` by its OWN
    predicate on the shapes it declares.  Where the rule is the pairs the
    solve's one call holds every head's systems and the chunk states alive
    are every head's."""
    _, params, _, _, _ = _build("bfloat16", **_WIDE)
    declared = kda_mod.kda.declares.recurrent
    assert declared.rule(params) == (64, 3, 16, 16, 256)
    assert declared.rule_applies is kr.kda_kernel_applies
    assert delta_mod.gated_delta.declares.recurrent.rule_applies \
        is recurrent.rule_kernel_applies
    assert recurrent.rule_kernel_layers(params, "tpu") == 1
    assert recurrent.rule_kernel_layers(params) == 0
    assert declared.solve(params, "tpu") == (64, 1 * 4 * 3)
    all_heads = recurrent.ssd_state_bytes(params)
    assert all_heads == 1 * 4 * 3 * 16 * 16 * 2       # one group holds three
    monkeypatch.setattr(kda_mod, "GROUP_BYTES", 256 * kda_mod._SUB * 16 * 4)
    assert recurrent.ssd_state_bytes(params) == all_heads // 3
    assert declared.solve(params) == (64, 4)
    assert declared.solve(params, "tpu") == (64, 12)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert recurrent.ssd_state_bytes(params) == all_heads
    assert declared.solve(params) == (64, 12)


@pytest.mark.parametrize("config,layers", [("kimi_linear_48b_a3b", 4),
                                           ("olmo_hybrid_7b", 3)])
def the_cells_rule_layers_test(config, layers):
    """The repo's Kimi-Linear cell: 4 ``kda`` layers take the pairs on a TPU,
    none on the CPU; Olmo-Hybrid's 3 / 0 stand."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           config + ".json")) as f:
        cell = json.load(f)["config"]
    params = ModelParameter({**cell, "model_path": "/tmp/" + config})
    assert recurrent.rule_kernel_layers(params, "tpu") == layers
    assert recurrent.rule_kernel_layers(params, "cpu") == 0
    assert recurrent.solve_kernel_layers(params, "tpu") == layers
    fact = next(f for f in recurrent.FACTS
                if f.metric == "hbnlp_delta_rule_kernel_layers")
    assert fact.value(params, None, "tpu") == layers
