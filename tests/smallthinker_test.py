"""SmallThinker-21BA3B-Instruct's layers through the normal path (ISSUE 72):
the program against the plain reference
``benchmark/reference/smallthinker_21b_a3b.py`` in logits, loss and every
gradient at toy widths; the router READS THE ATTENTION BLOCK'S INPUT and its
logits cross the block boundary as a carried side value (an operand of both
regions, no router matmul in the sparse block, the balance term's gradient
through it); ReLU-gated experts on the three held / unheld paths with the live
gate share counted; the eight shares add up to the uncut layer; the window at
reach / sub = 16 under 7 query heads a K/V head; refusals, scopes, facts and
the cut's parameter count."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import BlockArgs, ModelParameter
from homebrewnlp_tpu.core import scope
from homebrewnlp_tpu.core.tensor import nt
from homebrewnlp_tpu.model import Model, moe as moe_mod, route as route_mod
from homebrewnlp_tpu.model import spatial
from homebrewnlp_tpu.parallel import flash_attention as fa

CELL = "train_smallthinker_21b_ep8_s16k"
EARLY, SPARSE = "route_early", "moe-relu-routed_early"


def _period(window: int = 16, q_heads: int = 4, kv_heads: int = 2):
    """The published period of four layers: a global layer without positions,
    three windowed ones with rotary, each before a sparse block."""
    heads = f"q_heads{q_heads}-kv_heads{kv_heads}"
    layers = [f"attention-nope-{heads}"] \
        + [f"attention-rope-theta1500000-{heads}-window{window}"] * 3
    blocks = []
    for layer in layers:
        blocks += [{"skip": True, "layer": ["norm-rms-scale", EARLY, layer]},
                   {"skip": True, "layer": ["norm-rms-scale", SPARSE]}]
    return blocks


# 4 query heads over 2 K/V heads of 16 on a stream of 4 x 16, a window of 16
# in a sequence of 64; 24 routed experts (a size no other axis has) of which
# 3 are held, 3 a token: the row buffer is exactly an eighth full when the
# router is balanced, the cell's boundary of ``walks_real_rows``
TINY = {"depth": 2, "heads": 4, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 272,
        "experts": 24, "experts_held": 3, "moe_top_k": 3, "expert_width": 40,
        "tpu_size": 1, "use_checkpointing": False,
        "use_flash_attention": False, "block_config": _period()}
TOKENS, EXPERTS = 2 * 64, 24


def _reference():
    return harness.reference("smallthinker_21b_a3b")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("smallthinker_21b_a3b", TINY, dtype, **extra)


def _lively(variables, seed: int = 3):
    """The seeded weights with the routers' matrices scaled up: at
    normal(0.02) the logits lie ~0.01 apart and every choice is a near-tie."""
    return {name: jnp.asarray(np.asarray(value) * (
        25.0 if "/route_early_0/" in name else 1.0))
        for name, value in variables.items()}


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra), lively=_lively)


@pytest.fixture(scope="module")
def built():
    return _build()


def _grad(model, variables, batch):
    return jax.jit(jax.grad(
        lambda v: model.apply(v, batch).total_loss.data))(variables)


@pytest.fixture(scope="module")
def grads(built):
    _, _, model, batch, variables = built
    return _grad(model, variables, batch)


# ---- the program against the reference ---------------------------------------

def logits_and_loss_match_reference_test(built):
    """float32 against float32: only the order of sums differs, so this pins
    the equations — the router on the wrong block's input, a rotated global
    layer, an unrotated window layer, a window off by one, SiLU for ReLU,
    probabilities that are not renormalised are off by orders of magnitude."""
    got = harness.assert_program_matches_reference(_reference(), built,
                                                   "float32", 2e-5)
    assert got.shape == (2, 64, 272)


@pytest.mark.parametrize("dtype,tolerance,extra", [
    # every expert held (the uncut layer) and another rank's share
    ("float32", 2e-5, {"experts_held": 0}),
    ("float32", 2e-5, {"experts_first": 21}),
    # the configuration's bfloat16, at the cells' bound
    ("bfloat16", 2 ** -4, {})], ids=["all_held", "last_share", "bfloat16"])
def other_shares_and_bfloat16_match_reference_test(dtype, tolerance, extra):
    harness.assert_program_matches_reference(
        _reference(), _build(dtype, **extra), dtype, tolerance)


def loss_and_every_gradient_match_reference_test(built, grads):
    """Every parameter's gradient against ``jax.grad`` of the reference's
    ``train_loss`` (cross-entropy plus the balance term the step injects
    into the CARRIED logits' cotangent); every early router's matrix is
    alive."""
    config, params, _, batch, variables = built
    assert params.train and params.moe_balance_loss
    _, want = harness.reference_loss_and_grads(
        _reference(), variables, batch["token_x"][..., 0],
        batch["token_y"][..., 0], config)
    harness.assert_grads_match(grads, want, 2e-4)
    routers = [name for name in grads if "/route_early_0/" in name]
    assert len(routers) == 8 and not any("/moe_0/" in name and np.asarray(
        grads[name]).shape[-1] == EXPERTS for name in grads)
    for name in routers:
        assert float(jnp.max(jnp.abs(grads[name]))) > 0, name


def reference_at_the_next_precision_below_fails_test():
    """``harness.assert_float8_stream_misses``."""
    harness.assert_float8_stream_misses(_reference(), _build("bfloat16"))


# ---- the router reads the attention block's input ------------------------------

def _hidden_routed_late(ref, variables, tokens, config):
    """The reference with the OTHER placement: each sparse block's router
    (the same matrix) on that block's own normed input."""
    eps = float(config["norm_epsilon"])
    h = ref.common.param(variables,
                         "input0/gather0/embed0/normal_var0")[tokens]
    moved = 0
    for kind, p, spec in ref.layers_of(variables, config):
        if kind == "attention":
            out, early = ref.attention_block(p, h, spec, eps)
            w_router = p["w_router"]
        else:
            late = jnp.einsum("bsgf,gfe->bse",
                              ref.normed(h, p["w_norm"], eps), w_router)
            moved += int(jnp.sum(jnp.any(
                jax.lax.top_k(late, 3)[1] != jax.lax.top_k(early, 3)[1],
                axis=-1)))
            out, _ = ref.sparse_block(p, h, late, config)
        h = h + out
    return h, moved


def routing_on_the_sparse_blocks_input_chooses_otherwise_test(built):
    """A router on the sparse block's own input — the placement every other
    configuration has — makes other choices for many tokens and other logits;
    the program's are the early placement's."""
    config, _, model, batch, variables = built
    ref = _reference()
    tokens = batch["token_x"][..., 0]
    h, moved = _hidden_routed_late(ref, variables, tokens, config)
    assert moved > 8 * TOKENS // 10
    late = np.asarray(ref._logits(h, *ref._head(variables), 1e-6))
    want = np.asarray(ref.forward(variables, tokens, config))
    got, _ = harness.logits_and_loss(model, variables, batch)
    assert harness.error(got, want) < 2e-5 < 1e-3 < harness.error(late, want)


def _equations(jaxpr, found=None, inside=()):
    """``(equation, the names of the equations it lies in)`` of every
    equation under ``jaxpr``."""
    found = [] if found is None else found
    for eqn in jaxpr.eqns:
        found.append((eqn, inside))
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) \
                    else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    _equations(inner, found, inside + (eqn,))
    return found


def _shapes(variables):
    return [tuple(getattr(v.aval, "shape", ())) for v in variables]


def the_logits_cross_the_boundary_and_no_region_routes_twice_test(built):
    """In the gradient's jaxpr the float32 ``[tokens, experts]`` logits leave
    every attention block's ``jax.checkpoint`` region and enter every sparse
    block's; the ONLY matmuls with an ``experts`` axis are the early
    router's — forward and its two gradients a layer, none in a replay."""
    config, _, model, batch, variables = built
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda v: model.apply(v, batch).total_loss.data))(variables)
    found = _equations(jaxpr.jaxpr)
    regions = [e for e, _ in found
               if e.primitive.name in ("checkpoint", "remat", "remat2")]
    takes = [e for e in regions if (TOKENS, EXPERTS) in _shapes(e.invars)]
    gives = [e for e in regions if (TOKENS, EXPERTS) in _shapes(e.outvars)]
    layers = 4 * config["depth"]
    assert len(takes) >= layers and len(gives) >= layers
    routing = [(e, inside) for e, inside in found
               if e.primitive.name == "dot_general" and any(
                   EXPERTS in shape for shape in _shapes(e.invars)
                   + _shapes(e.outvars))]
    # a replay that made the logits again would be a fourth a layer; the
    # sparse blocks hold no parameter with an experts axis to route by
    # (loss_and_every_gradient_match_reference_test)
    assert len(routing) == 3 * layers
    for eqn, _ in routing:
        assert {TOKENS, 64} & set(sum(_shapes(eqn.invars), ())), eqn


def _sparse_layer(params, weights, x, logits, flags=("relu", "routed_early"),
                  stats=None):
    """Layer ``moe`` of ``params`` under ``flags`` on the normed ``x [b, s,
    heads, features]`` with the reference's matrices ``weights`` and the
    carried ``logits``: ``(output, the context it ran in)``."""
    names = dict(_reference().SPARSE)
    ctx = scope.Context("apply", params={
        path + "/var0": jnp.asarray(weights[short])
        for short, path in names.items()})
    ctx.side = {} if logits is None else {route_mod.ROUTER_LOGITS: logits}
    ctx.layer_stats = stats
    with scope.context(ctx):
        out = scope.scoped("moe_", moe_mod.moe, BlockArgs(
            params, nt(x, [params.batch_dim, params.sequence_dim]
                       + list(params.feature_dims)), list(flags)))
    return out.data, ctx


def _layer_inputs(seed: int, held: int, n_exp: int = EXPERTS):
    rng = np.random.default_rng(seed)

    def normal(*shape, scale=0.3):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * scale)

    weights = {"w_gate": normal(held, 4, 16, 40),
               "w_up": normal(held, 4, 16, 40),
               "w_down": normal(held, 40, 4, 16)}
    return weights, normal(2, 64, 4, 16, scale=1.0), \
        normal(2, 64, n_exp, scale=2.0)


def the_balance_terms_gradient_leaves_through_the_carried_value_test():
    """The sparse layer's cotangent for the logits it was HANDED: the routing
    weights' part, plus — in training, under ``moe_balance_loss`` — the
    balance term's, which the reference's own term gives; and the layer
    takes the value out of the side dict."""
    ref = _reference()
    weights, x, logits = _layer_inputs(11, 3)
    probe = jnp.asarray(np.random.default_rng(5).normal(size=x.shape),
                        jnp.float32)
    grads = {}
    # under a zero cotangent of the output only the balance term is left
    for balance, weight in ((0.0, 1.0), (0.01, 0.0)):
        params = ModelParameter(_config(moe_balance_loss=balance))

        def loss(logits):
            out, ctx = _sparse_layer(params, weights, x,
                                     logits.reshape(TOKENS, EXPERTS))
            assert route_mod.ROUTER_LOGITS not in ctx.side
            return jnp.sum(out * probe) * weight
        grads[balance] = jax.grad(loss)(logits)
    assert float(jnp.max(jnp.abs(grads[0.0]))) > 0
    want = jax.grad(lambda r: ref.route(r, 3, True, 0.01, 0.0)[1])(logits)
    assert float(jnp.max(jnp.abs(want))) > 0
    assert harness.error(grads[0.01], want) < 1e-4


def checkpoint_and_none_agree_test(built, grads):
    """The gradient under ``checkpoint`` (every block a region with the
    logits an operand out of one and into the next) equals ``none``'s."""
    _, _, model, batch, variables = _build(memory_reduction_strategy="none")
    harness.assert_grads_match(grads, _grad(model, variables, batch), 1e-5)


# ---- refusals ------------------------------------------------------------------

@pytest.mark.parametrize("extra,match", [
    ({"scan_layers": True}, "route_early.*carried side value"),
    ({"memory_reduction_strategy": "revnet"}, "route_early.*revnet"),
    ({"memory_reduction_strategy": "momentum"}, "route_early.*momentum")])
def modes_without_a_side_value_refuse_by_name_test(extra, match):
    config = _config(**extra)
    model = Model(ModelParameter(config))
    batch = harness.token_batch(2, 64)
    with pytest.raises(NotImplementedError, match=match):
        variables = model.init(batch, seed=1)
        jax.grad(lambda v: model.apply(v, batch).total_loss.data)(variables)


def the_sparse_flag_refuses_by_name_test():
    """Without a side dict (decode, prefill, the stats probe, a leading
    block), without a ``route_early`` before it, and beside the routers that
    have no early form."""
    params = ModelParameter(_config())
    weights, x, logits = _layer_inputs(1, 3)
    with pytest.raises(ValueError, match="routed_early found no carried "
                       "side value 'router_logits'.*route_early"):
        _sparse_layer(params, weights, x, None)
    with pytest.raises(ValueError, match="routed_early got logits"):
        _sparse_layer(params, weights, x, logits)      # not [tokens, experts]
    for flag in ("router_mlp", "sigmoid_bias"):
        with pytest.raises(NotImplementedError, match="routed_early"):
            _sparse_layer(params, weights, x, logits.reshape(TOKENS, EXPERTS),
                          ("relu", "routed_early", flag))
    tensor = nt(x, [params.batch_dim, params.sequence_dim]
                + list(params.feature_dims))
    ctx = scope.Context("init", params={})
    assert ctx.side is None
    with scope.context(ctx):
        with pytest.raises(NotImplementedError,
                           match="routed_early.*carried side value"):
            scope.scoped("moe_", moe_mod.moe,
                         BlockArgs(params, tensor, ["relu", "routed_early"]))
        with pytest.raises(NotImplementedError,
                           match="route_early.*carried side value"):
            scope.scoped("route_early_", route_mod.route_early,
                         BlockArgs(params, tensor, []))
    ctx.side = {}
    with scope.context(ctx), pytest.raises(ValueError, match="no flags"):
        scope.scoped("route_early_", route_mod.route_early,
                     BlockArgs(params, tensor, ["silu"]))
    with pytest.raises(ValueError, match="does not know flag"):
        Model(ModelParameter(_config(block_config=[
            {"skip": True, "layer": ["norm-rms-scale", "moe-relu-early"]}]))
              ).init(harness.token_batch(2, 64), seed=1)


# ---- ReLU on the three paths, and its counter ------------------------------------

@pytest.mark.parametrize("held,first,walks", [
    (0, 0, False), (3, 6, True), (12, 12, False)],
    ids=["all_held", "share_walked", "share_gathered"])
def relu_experts_on_every_path_test(held, first, walks, monkeypatch):
    """``down(relu(gate x) * up x)`` where every expert is held, where a
    share is held and the layer walks the real rows, and where it gathers the
    whole buffer: the value and the input's gradient against the reference's
    dense loop, and ``moe_gate_live`` / ``moe_gate_values`` against a numpy
    count over the routed pairs of the held experts.  The buffers start as
    NaN: whatever reads a row past the real ones fails."""
    ref = _reference()
    config = _config(experts_held=held, experts_first=first,
                     moe_balance_loss=0.0)
    params = ModelParameter(config)
    n_held = held or EXPERTS
    assert moe_mod.walks_real_rows(EXPERTS, held, 3) == walks
    weights, x, logits = _layer_inputs(held + 2, n_held)
    probe = jnp.asarray(np.random.default_rng(9).normal(size=x.shape),
                        jnp.float32)
    monkeypatch.setattr(jax.lax, "empty", lambda shape, dtype: jnp.full(
        shape, jnp.nan, dtype))

    def run(x):
        stats: list = []
        out, _ = _sparse_layer(params, weights, x,
                               logits.reshape(TOKENS, EXPERTS), stats=stats)
        merged = {k: v for part in stats for k, v in part.items()}
        return jnp.sum(out * probe), (out, merged)

    (_, (got, stats)), d_x = jax.jit(jax.value_and_grad(run, has_aux=True))(x)

    def want_of(x):
        # the reference's experts on the layer's own (already normed) input
        routed, _ = ref.route(logits, 3, True, 0.0, 0.0)
        out = jnp.zeros_like(x)
        for j in range(n_held):
            out = out + ref.one_expert(
                x, weights["w_gate"][j], weights["w_up"][j],
                weights["w_down"][j], routed[..., first + j])
        return out, routed
    want, routed = want_of(x)
    assert np.all(np.isfinite(np.asarray(got)))
    assert harness.error(got, want) < 1e-5
    want_dx = jax.grad(lambda x: jnp.sum(want_of(x)[0] * probe))(x)
    assert harness.error(d_x, want_dx) < 1e-5
    gate = np.einsum("bsgf,jgfi->bsji", np.asarray(x),
                     np.asarray(weights["w_gate"]))
    chosen = np.asarray(routed)[..., first:first + n_held] > 0
    assert float(stats["moe_gate_values"]) == chosen.sum() * 40
    assert float(stats["moe_gate_live"]) == (
        (gate > 0) & chosen[..., None]).sum()
    assert 0.3 < float(stats["moe_gate_live"] / stats["moe_gate_values"]) < 0.7


def the_step_reports_the_live_gate_share_and_the_whole_load_test(built):
    """``hbnlp_moe_gate_live_share`` over the relu-gated layers of a step,
    and ``moe_all_load_max_over_mean`` over ALL the experts (the held share
    sees an eighth of the load)."""
    _, _, model, batch, variables = built
    info = harness.apply_with_stats(model, variables, batch)
    live = np.asarray(info.layer_stats["moe_gate_live"])
    values = np.asarray(info.layer_stats["moe_gate_values"])
    held = np.asarray(info.layer_stats["moe_held_pairs"])
    assert live.shape == (8,) and np.array_equal(values, held * 40)
    from homebrewnlp_tpu.train import _LAYER_STATS, _info_metrics
    metrics = _info_metrics(info)
    assert float(metrics["moe_gate_live_share"]) == pytest.approx(
        live.sum() / values.sum())
    assert 0.4 < float(metrics["moe_gate_live_share"]) < 0.6
    assert np.asarray(info.layer_stats["moe_all_load_max_over_mean"]
                      ).shape == (8,)
    assert float(metrics["moe_all_load_max_over_mean"]) >= 1.0
    assert {"moe_gate_live_share", "moe_all_load_max_over_mean",
            "moe_held_row_tiles"} <= set(_LAYER_STATS)
    # a silu-gated layer reports none of it
    stats: list = []
    weights, x, logits = _layer_inputs(4, 3)
    _sparse_layer(ModelParameter(_config()), weights, x,
                  logits.reshape(TOKENS, EXPERTS), ("silu", "routed_early"),
                  stats)
    assert not any("moe_gate_live" in part for part in stats)


# ---- the shares ------------------------------------------------------------------

def eight_shares_add_up_to_the_uncut_layer_test():
    """The guide's share test: eight ranks of an expert-parallel group, each
    with 3 of the 24 experts (8 of 64 in the cell): their parts of one sparse
    layer add up to what the uncut layer gives — the router, whole on every
    rank, chooses alike and is counted once — in the PROGRAM's layer and in
    the reference's."""
    ref = _reference()
    whole, x, logits = _layer_inputs(21, EXPERTS)
    flat = logits.reshape(TOKENS, EXPERTS)
    uncut, _ = _sparse_layer(ModelParameter(_config(experts_held=0)), whole,
                             x, flat)
    parts, ref_parts = [], []
    ones = jnp.ones((4, 16), jnp.float32)
    for first in range(0, EXPERTS, 3):
        share = {name: value[first:first + 3] for name, value in whole.items()}
        config = _config(experts_held=3, experts_first=first)
        parts.append(_sparse_layer(ModelParameter(config), share, x, flat)[0])
        # the reference norms its input: a scale of one on a stream that was
        # normed before is the stream again only up to eps, so compare the
        # reference's shares with the reference's whole
        ref_parts.append(ref.sparse_block({**share, "w_norm": ones}, x,
                                          logits, config)[0])
    ref_whole = ref.sparse_block({**whole, "w_norm": ones}, x, logits,
                                 _config(experts_held=0))[0]
    assert float(jnp.max(jnp.abs(uncut))) > 1e-3
    assert harness.error(sum(parts), uncut) < 1e-5
    assert harness.error(sum(ref_parts), ref_whole) < 1e-5
    # no rank's part is the whole: each leaves the others' experts out
    assert all(harness.error(part, uncut) > 1e-2 for part in parts)


# ---- the window at the cell's reach over its sub-block --------------------------

@pytest.fixture
def band_sub_16(monkeypatch):
    """Sub-blocks of 16 rows, so that a window of 256 is the cell's reach /
    sub = 16 (4,096 / 256) at a toy sequence."""
    monkeypatch.setattr(fa, "_BAND_SUB", 16)


@pytest.mark.parametrize("band", [True, False], ids=["band", "tiled"])
def the_window_at_sixteen_sub_blocks_of_reach_test(band, band_sub_16,
                                                   monkeypatch):
    """7 query heads a K/V head (repeated for the kernels, as
    ``causal_heads`` does), a window whose reach is 16 sub-blocks: the band
    forward's one-pass softmax over ``16 x (16 + 256)`` scores a sub-block
    and the tiled form, and the fused windowed backward, interpreted,
    against ``_xla_reference``."""
    s, window, group, d = 512, 256, 7, 16
    if not band:
        monkeypatch.setattr(fa, "band_applies", lambda *a, **kw: False)
    sub, reach, span = fa._band_geometry(s, window, 64)
    assert (sub, reach // sub, span) == (16, 16, 272)
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, s, group, d)), jnp.float32)
    k, v = (jnp.repeat(jnp.asarray(rng.normal(size=(1, s, 1, d)),
                                   jnp.float32), group, axis=2)
            for _ in range(2))
    do = jnp.asarray(rng.normal(size=q.shape), jnp.float32)
    got, pull = jax.vjp(lambda q, k, v: fa.flash_attention(
        q, k, v, 0.25, True, 64, 64, True, None, None, window), q, k, v)
    want, want_pull = jax.vjp(lambda q, k, v: fa._xla_reference(
        q, k, v, 0.25, True, window), q, k, v)
    harness.assert_close_each((got, *pull(do)), (want, *want_pull(do)), 2e-5,
                              ("out", "dq", "dk", "dv"))


def a_window_as_long_as_the_sequence_is_causal_test(band_sub_16):
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 128, 7, 16)), jnp.float32)
               for _ in range(3))
    for window in (128, 4096):
        np.testing.assert_array_equal(
            np.asarray(fa.attention(q, k, v, window=window)),
            np.asarray(fa.attention(q, k, v)))
    wide = np.asarray(fa._xla_reference(q, k, v, 0.25, True, 128))
    np.testing.assert_array_equal(
        wide, np.asarray(fa._xla_reference(q, k, v, 0.25, True, None)))
    assert harness.error(fa._xla_reference(q, k, v, 0.25, True, 127),
                         wide) > 1e-4


# ---- the cell: facts, scopes, parameters, the kernels at the published widths ----

def _cell_params(**extra):
    from benchmark.lib.cell import load_cell
    cell = load_cell(CELL)
    return cell, ModelParameter({**cell.model_config(), **extra,
                                 "model_path": "/tmp/smallthinker_test"})


def the_cells_facts_test():
    """Six band layers, the carried logits' bytes, the row buffer at exactly
    an eighth (walked), the attention kind alone inside the 15%."""
    from homebrewnlp_tpu.model import remat
    cell, params = _cell_params()
    assert spatial.flash_band_layers(params, "tpu") == 6
    assert spatial.flash_band_layers(params, "cpu") == 0
    assert fa.band_applies(16384, 128, 4096, 2)
    assert fa._band_geometry(16384, 4096, fa.band_block(16384)) \
        == (256, 4096, 4352)
    # eight layers x float32 [1, 16384, 64]
    assert moe_mod.router_carry_bytes(params) == 8 * 16384 * 64 * 4
    assert moe_mod.walks_real_rows(64, 8, 6)
    assert not moe_mod.walks_real_rows(64, 9, 6)
    assert moe_mod.moe_held_rows(params) == 16384 * 6
    plan = remat.stash_plan(params)
    assert plan["attention"] == (8, 8 * 28 * 16384 * (128 * 2 + 4))
    assert plan["experts"] == (0, 0)
    # the memory rule charges the carried logits beside the sixteen regions'
    # block inputs (bfloat16 [1, 16384, 2560] each)
    assert remat._block_input_bytes(params, 1) \
        == 16 * 16384 * 2560 * 2 + 8 * 16384 * 64 * 4
    # a ZAYA1-like stack carries router states as before, and both kinds add
    zaya = ModelParameter(harness.config_of("zaya1_8b", {
        "depth": 3, "sequence_length": 64, "train_batch_size": 2,
        "moe_router_width": 16}))
    assert moe_mod.router_carry_bytes(zaya) == 2 * 2 * 64 * 16 * 4
    assert cell.spec["rehearsal"]["config"]["block_config"][1]["layer"][1] \
        == SPARSE


def the_new_scopes_fold_test(built):
    assert scope_key("gpt0/body0/block0_0_0/route_early_0/dot_general") \
        == "body/route_early"
    assert scope_key("transpose(jvp(gpt0))/body0/block1_2_0/route_early_0/"
                     "dot_general") == "body/route_early"
    assert scope_key("gpt0/body0/block0_1_0/moe_0/router/carried/x") \
        == "body/moe/router/carried"
    assert scope_key("gpt0/body0/block0_1_0/moe_0/router/softmax") \
        == "body/moe/router"
    _, _, model, batch, variables = built
    keys = {scope_key(name) for name in harness.traced_op_names(
        model, variables, batch, compiled=False)}
    assert {"body/route_early", "body/moe/router", "body/moe/experts",
            "body/attention", "head_loss"} <= keys


def the_cut_holds_the_issues_parameters_test():
    """The shapes the program builds for the cell: ISSUE 72's 68,326,400 a
    layer, eight layers, two table slices and the final norm: 643,852,800,
    the count ``benchmark/configs/smallthinker_21b_a3b.json`` states."""
    cell, params = _cell_params()
    stated = cell.config_doc["parameters"]
    batch = {k: np.zeros((1, 16384, 1), np.int32)
             for k in ("token_x", "token_y")}
    shapes = jax.eval_shape(lambda b: Model(params).init(b, seed=1), batch)
    count = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert sum(stated["a_layer"].values()) == 68_326_400
    assert count == 8 * 68_326_400 + stated["tables_and_final_norm"] \
        == stated["program"] == stated["counted"] == stated["issue_72"] \
        == 643_852_800
    routers = [v.shape for name, v in shapes.items()
               if "/route_early_0/" in name]
    assert routers == [(20, 128, 64)] * 8
    # the whole model of configs/: depth 13, all experts, all rows
    whole = ModelParameter({**harness.config_of(
        "smallthinker_21b_a3b", {}, "bfloat16"), "model_path": "/tmp/st"})
    assert (whole.depth, whole.experts_held, whole.vocab_size) \
        == (13, 0, 151936)


def the_windowed_kernels_compile_at_the_published_widths_test(v5e,
                                                              monkeypatch):
    """A window layer's block of the cell — norm, the early router, 28 / 4
    heads of 128 with rotary over 16,384 positions under a window of 4,096 —
    traced as a TPU process traces it and compiled for a v5e: the band
    forward (its whole K and V and a 256 x 4,352 tile of scores in VMEM) and
    the fused windowed backward, both under the names the readers cost (the
    early router's logits feed nothing in a block compiled alone)."""
    _, hlo = harness.cell_layer_hlo(v5e, monkeypatch, CELL, 2, depth=1)
    calls = harness.kernel_calls(hlo)
    assert sorted(name for name, _ in calls) \
        == ["flash_bwd_fused_window", "flash_fwd_window"]
    assert all(scope_key(op_name) == "body/attention" for _, op_name in calls)
