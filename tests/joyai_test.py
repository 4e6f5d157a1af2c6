"""JoyAI-LLM-Flash through the normal path (ISSUE 65): the program against the
plain reference ``benchmark/reference/joyai_llm_flash.py`` in the main
logits, the multi-token-prediction module's logits, both losses and EVERY
gradient of ``L_main + 0.3 L_mtp`` at toy widths; the latent attention's
``q_latent`` / ``rope`` against a hand-written einsum form; the 16 expert
shares (the shared expert counted once) add up to the uncut layer; the last
position's weight 0; ``mtp_depth`` 0 and the flag-less latent form trace to
the parent's jaxprs; refusals, scopes, statistics, the memory rule's counts,
the repo's configuration."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from harness import REPO
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import BlockArgs, ModelParameter
from homebrewnlp_tpu.core import scope
from homebrewnlp_tpu.core.tensor import nt
from homebrewnlp_tpu.model import Model, moe as moe_mod, remat, spatial
from homebrewnlp_tpu.model.declare import fold_stats
from homebrewnlp_tpu.optim import own_rule
from homebrewnlp_tpu.train import Trainer

MOE = "moe-sigmoid_bias-shared_expert"
# 4 heads at key 16 + 8 rotary / value 16 from a K/V latent of 24 and a query
# latent of 40
MLA = "attention-rope-theta32000000-q_heads4-kv_heads4-kv_latent24" \
    "-shared_key8-q_latent40"
NOPE = "attention-nope-q_heads4-kv_heads4-kv_latent24-shared_key8"


def _block(layer):
    return {"skip": True, "layer": ["norm-rms-scale", layer]}


# a stream of 2 x 16; layer 0 with a dense MLP of 112, two sparse layers (16
# routed experts of 24, 4 held, 4 a token, a shared expert of 40), the module
TINY = {"depth": 2, "heads": 2, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 384,
        "experts": 16, "experts_held": 4, "moe_top_k": 4, "expert_width": 24,
        "shared_expert_width": 40, "tpu_size": 1, "use_checkpointing": False,
        "input_block_config": [_block(MLA), _block("mlp-silu")],
        "block_config": [_block(MLA), _block(MOE)],
        "mtp_block_config": [_block(MLA), _block(MOE)]}


def _reference():
    return harness.reference("joyai_llm_flash")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("joyai_llm_flash", TINY, dtype, **extra)


def _lively(variables, bias: float = 0.05):
    """At normal(0.02) a sigmoid router's scores are all but 0.5: the
    router's matrix scaled up makes the choice depend on the token, and a
    selection bias that is not zero makes it differ from the scores' own."""
    rng = np.random.default_rng(1)
    out = {}
    for name, value in variables.items():
        if name.endswith("moe_0/normal_var0/var0"):
            value = value * 30
        elif moe_mod.SELECTION_BIAS in name:
            value = (rng.normal(size=value.shape) * bias).astype(np.float32)
        out[name] = value
    return out


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra), lively=_lively)


@pytest.fixture(scope="module")
def built():
    """The float32 toy model, once a module."""
    return _build()


def _tokens(batch):
    return batch["token_x"][..., 0], batch["token_y"][..., 0]


# ---- the program against the reference ---------------------------------------

@pytest.mark.parametrize("dtype,tolerance,extra", [
    # float32 against float32 pins the EQUATIONS: a query without its
    # latent's norm, a key part turned a head, the first 8 query features
    # turned for the last, theta 10,000 are off by orders of magnitude
    ("float32", 2e-5, {}),
    ("float32", 2e-5, {"experts_held": 0}),
    ("float32", 2e-5, {"experts_held": 4, "experts_first": 8}),
    # bfloat16 rounds every activation to 8 bits of mantissa: the cells'
    # bound at toy sizes (benchmark/tests/reference_test.py)
    ("bfloat16", 2 ** -4, {})],
    ids=["float32", "all_held", "third_share", "bfloat16"])
def main_logits_and_loss_match_reference_test(dtype, tolerance, extra):
    harness.assert_program_matches_reference(
        _reference(), _build(dtype, **extra), dtype, tolerance)


def _module_logits(model, variables, batch):
    """The module's logits from the program: its stream after its own last
    norm times the head, both read off the build."""
    from homebrewnlp_tpu.model import mtp

    def run(v, b):
        kept = {}
        real = mtp._head_loss

        def spy(params, stream, head, targets, ahead):
            kept["logits"] = jnp.einsum(
                "bshk,hkpv->bspv", stream.data.astype(jnp.float32),
                head.data.astype(jnp.float32))[:, :, 0]
            return real(params, stream, head, targets, ahead)

        mtp._head_loss = spy
        try:
            info = model.apply(v, b, layer_stats=True)
        finally:
            mtp._head_loss = real
        return kept["logits"], info.layer_stats, info.total_loss.data, \
            info.objective.data

    return jax.jit(run)(variables, batch)


@pytest.mark.parametrize("dtype,tolerance", [
    # float32: summation order only.  bfloat16: the module reads the main
    # stream after 2.5 layers' rounding and adds a layer's own
    ("float32", 2e-5), ("bfloat16", 2 ** -4)])
def module_logits_and_both_losses_match_reference_test(dtype, tolerance):
    config, _, model, batch, variables = _build(dtype)
    ref = _reference()
    tokens, targets = _tokens(batch)
    got, stats, main_loss, objective = _module_logits(model, variables, batch)
    want = ref.mtp_forward(variables, tokens, targets, config)
    assert harness.error(got, want) < tolerance
    want_mtp = float(ref.mtp_loss_of(want, targets, 0.0))
    want_main = float(harness.reference("common").loss_of(
        ref.forward(variables, tokens, config), targets, 0.0))
    ulp = 2.0 ** -18 if dtype == "float32" else 2.0 ** -5
    # L_mtp leaves the chunked walk in float32 whatever the dtype; the
    # bfloat16 bound is the logits' rounding
    assert abs(float(stats["mtp_loss"][0]) - want_mtp) <= 8 * ulp
    assert abs(float(main_loss) - want_main) <= ulp
    # the reported loss is L_main, the objective L_main + 0.3 L_mtp
    assert float(objective) == pytest.approx(
        float(main_loss) + 0.3 * float(stats["mtp_loss"][0]), rel=1e-6)
    folded = fold_stats(stats)
    assert float(folded["mtp_loss_over_main"]) == pytest.approx(
        float(stats["mtp_loss"][0]) / float(main_loss), rel=1e-6)


@pytest.mark.parametrize("extra", [
    {}, {"memory_reduction_strategy": "none"}, {"remat_policy": "stash"}],
    ids=["checkpoint", "no_replay", "saved"])
def every_gradient_of_the_objective_matches_reference_test(extra):
    """The step's gradient is ``L_main + 0.3 L_mtp``'s (+ the balance
    terms'): every parameter against ``jax.grad`` of the reference's
    ``train_loss``.  2e-4 of a gradient's largest entry: float32 summation
    order through three layers and two head walks (measured 2e-6)."""
    config, params, model, batch, variables = _build(**extra)
    ref = _reference()
    tokens, targets = _tokens(batch)
    trainer = Trainer(params, model)
    got, _ = jax.jit(lambda v, b: trainer._grads(v, b, None))(variables, batch)
    _, want = harness.reference_loss_and_grads(ref, variables, tokens,
                                               targets, config)
    counts = ref.pair_counts(variables, tokens, targets, config)
    biases = sorted(k for k in got if own_rule(k))
    assert set(got) == set(want) and len(biases) == len(counts) == 3
    for name in got:
        if own_rule(name):
            continue
        assert float(jnp.max(jnp.abs(want[name]))) > 0, name
        assert harness.error(got[name], want[name]) < 2e-4, name
    # the selection bias has no gradient: the program hands the optimizer
    # the step's pair counts in its place, the module's layer as the body's
    by_order = sorted(biases, key=lambda n: ("mtp0" in n, n))
    for name, layer_counts in zip(by_order, counts):
        assert float(jnp.max(jnp.abs(want[name]))) == 0.0
        np.testing.assert_array_equal(np.asarray(got[name]),
                                      np.asarray(layer_counts))


def reference_at_the_next_precision_below_fails_test():
    """``harness.assert_float8_stream_misses``."""
    harness.assert_float8_stream_misses(_reference(), _build("bfloat16"))


def the_last_position_weighs_nothing_test():
    """The module's walk of the head (``mtp._head_loss``): the mean over the
    positions that have a target; what stands at the last one (the target
    that wrapped round) moves nothing, what stands one before it does."""
    from homebrewnlp_tpu.model import mtp
    params = ModelParameter(_config())
    rng = np.random.default_rng(4)
    dims = [params.batch_dim, params.sequence_dim]
    stream = nt(jnp.asarray(rng.normal(size=(2, 64, 2, 16)), jnp.float32),
                dims + list(params.feature_dims))
    head = nt(jnp.asarray(rng.normal(size=(2, 16, 1, 384)), jnp.float32),
              list(params.feature_dims) + [params.token_patch_dim,
                                           params.vocab_dim])
    targets = rng.integers(0, 384, (2, 64, 1)).astype(np.int32)

    @jax.jit
    def loss_and_grad(targets):
        return jax.value_and_grad(lambda x: mtp._head_loss(
            params, nt(x, stream.dims), head, nt(targets, dims + [
                params.token_patch_dim]), 1))(stream.data)

    base, grad = loss_and_grad(targets)
    logits = jnp.einsum("bshk,hkv->bsv", stream.data, head.data[:, :, 0])
    want = harness.reference("common").loss_of(logits[:, :-1],
                                               targets[:, :-1, 0], 0.0)
    assert float(base) == pytest.approx(float(want), rel=1e-6)
    assert float(jnp.max(jnp.abs(grad[:, -1]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(grad[:, :-1]), axis=(2, 3)))) > 0
    for position, moves in ((-1, False), (-2, True)):
        other = targets.copy()
        other[:, position] = (other[:, position] + 7) % 384
        assert (float(loss_and_grad(other)[0]) != float(base)) == moves


def the_module_reads_no_later_token_test(built):
    """Position ``i`` of the module's logits reads ``token_x[.. i]`` and
    ``token_y[.. i]``: tokens after it move nothing before them."""
    config, _, model, batch, variables = built
    got, *_ = _module_logits(model, variables, batch)
    later = {k: v.copy() for k, v in batch.items()}
    later["token_x"][:, 40:] = (later["token_x"][:, 40:] + 3) % 256
    later["token_y"][:, 40:] = (later["token_y"][:, 40:] + 3) % 256
    moved, *_ = _module_logits(model, variables, later)
    np.testing.assert_array_equal(np.asarray(got[:, :40]),
                                  np.asarray(moved[:, :40]))
    assert float(jnp.max(jnp.abs(got[:, 40:] - moved[:, 40:]))) > 0


# ---- the latent form against a hand-written einsum form ----------------------------

def _by_hand(w, x, q_latent: bool, rope: bool, eps=1e-6, theta=32e6):
    """``_latent_attention`` as plain einsums: ``x [b, s, g, f]``."""
    def rms(t, scale):
        return t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True)
                                 + eps) * scale

    def turn(t):
        half = t.shape[-1] // 2
        angle = jnp.arange(t.shape[1], dtype=jnp.float32)[:, None] \
            * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
        cos, sin = (f(angle)[None, :, None, :] for f in (jnp.cos, jnp.sin))
        a, b = t[..., :half], t[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    if q_latent:
        c_q = rms(jnp.einsum("bsgf,gfc->bsc", x, w["qa"]), w["q_scale"])
        q = jnp.einsum("bsc,chd->bshd", c_q, w["qb"])
    else:
        q = jnp.einsum("bsgf,gfhd->bshd", x, w["q"])
    down = jnp.einsum("bsgf,gfc->bsc", x, w["down"])
    c = w["scale"].shape[0]
    up = jnp.einsum("bsc,chd->bshd", rms(down[..., :c], w["scale"]), w["up"])
    d = up.shape[-1] // 2
    shared = down[:, :, None, c:]
    if rope:
        q = jnp.concatenate([q[..., :d], turn(q[..., d:])], -1)
        shared = turn(shared)
    k = jnp.concatenate([up[..., :d], jnp.broadcast_to(
        shared, up.shape[:3] + shared.shape[-1:])], -1)
    score = jnp.einsum("bshd,bthd->bhst", q, k) * q.shape[-1] ** -0.5
    s = x.shape[1]
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    o = jnp.einsum("bhst,bthd->bshd", jax.nn.softmax(score, -1), up[..., d:])
    return jnp.einsum("bshd,hdgf->bsgf", o, w["out"])


@pytest.mark.parametrize("layer,q_latent,rope", [
    (MLA, True, True),
    (MLA.replace("-q_latent40", ""), False, True),
    (NOPE + "-q_latent40", True, False),
    (NOPE, False, False)],
    ids=["q_latent_rope", "rope", "q_latent_nope", "kimis_form"])
def latent_attention_is_the_hand_written_form_test(layer, q_latent, rope):
    rng = np.random.default_rng(3)

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)

    w = {"down": normal(2, 16, 32), "scale": 1 + normal(24),
         "up": normal(24, 4, 32), "out": normal(4, 16, 2, 16)}
    if q_latent:
        w.update(qa=normal(2, 16, 40), q_scale=1 + normal(40),
                 qb=normal(40, 4, 24))
        order = ["qa", "q_scale", "qb", "down", "scale", "up", "out"]
    else:
        w["q"] = normal(2, 16, 4, 24)
        order = ["q", "down", "scale", "up", "out"]
    names = {short: f"attention_0/normal_var{i}"
             for i, short in enumerate(order)}
    x = normal(2, 64, 2, 16)
    params = ModelParameter(_config(block_config=[_block(layer)]))
    got, _ = harness.layer_on(params, spatial.attention, names, w, x,
                              layer.split("-")[1:])
    assert harness.error(got, _by_hand(w, x, q_latent, rope)) < 2e-5


def the_latent_forms_parameters_come_in_creation_order_test():
    """``W_qa``, the query latent's scale, ``W_qb``, ``W_kva``, the latent's
    scale, ``W_kvb``, ``W_o``."""
    params = ModelParameter(_config(block_config=[_block(MLA)], mtp_depth=0,
                                    input_block_config=[]))
    variables = Model(params).init(harness.token_batch(2, 64), seed=3)
    shapes = {n.split("attention_0/")[1]: v.shape
              for n, v in variables.items() if "attention_0/" in n}
    assert shapes == {
        "normal_var0/var0": (2, 16, 40), "normal_var1/var0": (40,),
        "normal_var2/var0": (40, 4, 24), "normal_var3/var0": (2, 16, 32),
        "normal_var4/var0": (24,), "normal_var5/var0": (24, 4, 32),
        "normal_var6/var0": (4, 16, 2, 16)}


# ---- the share test --------------------------------------------------------------

def the_16_expert_shares_add_up_to_the_uncut_layer_test():
    """Sixteen expert-parallel ranks of four experts each: their routed
    parts, with what every rank computes alike (the shared expert) counted
    once, add up to what the uncut reference gives for the whole layer."""
    from benchmark.reference import kimi_linear_48b_a3b as shared_ref
    rng = np.random.default_rng(2)
    heads, width, n_exp, inter, shared, ranks = 2, 16, 64, 24, 40, 16

    def normal(*shape):
        return jnp.asarray(rng.normal(size=shape).astype(np.float32) * 0.3)

    whole = {"w_router": normal(heads, width, n_exp), "bias": normal(n_exp),
             "w_gate": normal(n_exp, heads, width, inter),
             "w_up": normal(n_exp, heads, width, inter),
             "w_down": normal(n_exp, inter, heads, width),
             "s_gate": normal(heads, width, shared),
             "s_up": normal(heads, width, shared),
             "s_down": normal(shared, heads, width),
             "w_norm_in": jnp.ones((heads, width))}
    h = normal(2, 64, heads, width)
    x = shared_ref.rms(h, whole["w_norm_in"], 1e-6)
    config = _config(experts=n_exp, experts_held=0, moe_top_k=8)
    uncut, _, _ = shared_ref.sparse_block(whole, h, config)
    shared_part = shared_ref.swiglu(x, whole["s_gate"], whole["s_up"],
                                    whole["s_down"])
    flags = MOE.split("-")[1:]
    held = n_exp // ranks
    total = np.asarray(shared_part)
    for rank in range(ranks):
        first = held * rank
        cut = _config(experts=n_exp, experts_held=held, experts_first=first,
                      moe_top_k=8)
        share = dict(whole, **{k: whole[k][first:first + held]
                               for k in ("w_gate", "w_up", "w_down")})
        want, _, _ = shared_ref.sparse_block(share, h, cut)
        if rank in (0, 7, 15):
            got, _ = harness.layer_on(ModelParameter(cut), moe_mod.moe,
                                      shared_ref.SPARSE, share, x, flags)
            assert harness.error(got, want) < 2e-5
        total = total + np.asarray(want - shared_part)
    assert harness.error(total, uncut) < 2e-5


# ---- refusals ------------------------------------------------------------------

@pytest.mark.parametrize("layer,match", [
    (MLA + "-rms", "does not know flag"),
    (MLA.replace("rope", "yarn"), "does not build yarn"),
    (MLA + "-rotary_pct50", "does not build rotary_pct"),
    (MLA + "-window32", "does not build window"),
    (MLA + "-qk_norm", "does not build qk_norm"),
    (MLA + "-gate", "does not build gate"),
    (MLA + "-indexed", "does not build indexed"),
    (MLA.replace("kv_heads4", "kv_heads2"), "kv_heads = q_heads"),
    ("attention-rope-q_latent40", "comes with kv_latent"),
    (MLA.replace("q_latent40", "q_latent0"), "are positive"),
    (MLA.replace("shared_key8", "shared_key7"), "even and positive"),
    (MLA.replace("-shared_key8", ""), "even and positive"),
    (NOPE + "-theta10000", "nope .* with rotary_pct / theta")],
    ids=["unknown", "yarn", "rotary_pct", "window", "qk_norm", "gate",
         "indexed", "grouped", "q_latent_alone", "q_latent_0", "odd_shared",
         "rope_without_shared", "nope_theta"])
def flags_refuse_by_name_test(layer, match):
    config = _config(block_config=[_block(layer)], mtp_depth=0)
    tokens = np.zeros((2, 64, 1), np.int32)
    with pytest.raises(ValueError, match=match):
        Model(ModelParameter(config)).init(
            {"token_x": tokens, "token_y": tokens}, seed=1)


@pytest.mark.parametrize("extra,match", [
    ({"loop_steps": 2}, "a looped model"),
    ({"memory_reduction_strategy": "revnet"}, "revnet"),
    ({"scan_layers": True}, "scan_layers"),
    ({"mtp_block_config": []}, "no mtp_block_config"),
    ({"multi_loss_strategy": "pcgrad"}, "multi_loss_strategy"),
    ({"mtp_loss_weight": -1}, "mtp_loss_weight"),
    ({"mtp_depth": -1}, "mtp_depth"),
    ({"mtp_depth": 1.5}, "mtp_depth"),
    ({"mesh_shape_override": {"pipe": 2, "data": 1}, "tpu_size": 2,
      "depth": 2}, "pipeline mesh")],
    ids=["looped", "revnet", "scan", "no_blocks", "pcgrad", "weight",
         "negative", "fraction", "pipeline"])
def the_module_refuses_by_name_what_it_does_not_build_test(extra, match):
    with pytest.raises(ValueError, match=match):
        ModelParameter(_config(**extra))


def decode_and_prefill_refuse_the_module_at_the_call_test(built):
    _, params, model, batch, variables = built
    with pytest.raises(NotImplementedError, match="multi-token-prediction"):
        model.apply_decode(variables, batch["token_x"][:, :1],
                           jnp.int32(0), {})
    with pytest.raises(NotImplementedError, match="multi-token-prediction"):
        model.apply_prefill(variables, batch["token_x"], jnp.int32(4))


# ---- what the new keys leave alone ----------------------------------------------

def without_the_module_the_step_traces_as_on_the_parent_test():
    """``mtp_depth`` 0 (Kimi-Linear's toy model, which has no module and the
    flag-less latent form): the forward's jaxpr is the parent's."""
    from kimi_linear_test import _config as kimi_config
    config, _, model, batch, variables = harness.build(kimi_config())
    assert config.get("mtp_depth", 0) == 0
    harness.pinned("step/kimi_linear_toy/no_mtp_module",
                   harness.step_jaxpr(model, variables, batch))


def the_flagless_latent_form_traces_as_on_the_parent_test():
    """Layer ``attention`` under Kimi-Linear's flags (``nope``, no query
    latent), in Kimi-Linear's toy configuration: the parent's jaxpr."""
    from kimi_linear_test import _config as kimi_config
    params = ModelParameter(kimi_config(block_config=[_block(NOPE)]))
    dims = [params.batch_dim, params.sequence_dim] + list(params.feature_dims)

    def run(x):
        with scope.context(scope.Context("init", seed=1)):
            return scope.scoped("attention_", spatial.attention, BlockArgs(
                params, nt(x, dims), NOPE.split("-")[1:])).data

    harness.pinned("layer/joyai/flagless_latent",
                   str(jax.make_jaxpr(run)(jnp.zeros((2, 64, 2, 16)))))


# ---- scopes, statistics, the memory rule ------------------------------------------

@pytest.mark.parametrize("path,scope_name", [
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/q_down/dot_general",
     "body/attention/q_down"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/q_norm/mul",
     "body/attention/q_norm"),
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/latent_rope/cos",
     "body/attention/latent_rope"),
    ("jit(step_fn)/jvp(gpt0)/input0/lang_inp0_0/attention_0/q_down/dot_general",
     "body/attention/q_down"),
    # the standard attention's rotary stays where it was
    ("jit(step_fn)/jvp(gpt0)/body0/block0_0_0/attention_0/rope/cos",
     "body/attention"),
    ("jit(step_fn)/jvp(gpt0)/mtp0/join/norm_0/mul", "mtp/join"),
    ("jit(step_fn)/jvp(gpt0)/mtp0/join/dot_general", "mtp/join"),
    ("jit(step_fn)/transpose(jvp(gpt0))/mtp0/body0/checkpoint/block0_0_0/"
     "attention_0/q_proj/dot_general", "mtp/body/attention/q_proj"),
    ("jit(step_fn)/jvp(gpt0)/mtp0/body0/block0_0_0/attention_0/attend/"
     "flash_attention/flash_fwd_causal", "mtp/body/attention"),
    ("jit(step_fn)/jvp(gpt0)/mtp0/body0/block0_1_0/moe_0/experts/gmm",
     "mtp/body/moe/experts"),
    ("jit(step_fn)/jvp(gpt0)/mtp0/output0/norm_0/mul", "mtp/output"),
    ("jit(step_fn)/jvp(gpt0)/mtp0/head_loss/while/dot_general",
     "mtp/head_loss"),
    ("jit(step_fn)/jvp(gpt0)/mtp0/add", "mtp"),
    ("jit(step_fn)/jvp(gpt0)/loss0/head_loss/while/dot_general", "head_loss"),
    ("jit(step_fn)/optimizer/gpt0/mtp0/normal_var0/mul", "optimizer")])
def the_new_scopes_fold_test(path, scope_name):
    assert scope_key(path) == scope_name


def traced_ops_carry_the_new_scopes_test(built):
    _, _, model, batch, variables = built
    found = {scope_key(name) for name in harness.traced_op_names(
        model, variables, batch, compiled=False)}
    assert {"body/attention/q_down", "body/attention/q_norm",
            "body/attention/q_proj", "body/attention/kv_down",
            "body/attention/kv_norm", "body/attention/kv_up",
            "body/attention/latent_rope", "body/attention/out_proj",
            "body/attention", "body/moe/shared", "body/moe/router",
            "body/mlp", "head_loss"} <= found


def the_objectives_ops_carry_the_modules_scopes_test(built):
    _, _, model, batch, variables = built
    import re
    lowered = jax.jit(jax.grad(
        lambda v, b: model.apply(v, b).objective.data)).lower(variables, batch)
    found = {scope_key(name) for name in re.findall(
        r'loc\("([^"]+)"', lowered.as_text(debug_info=True))}
    assert {"mtp/join", "mtp/body/attention/q_down", "mtp/body/attention",
            "mtp/body/moe/router", "mtp/body/moe/shared", "mtp/output",
            "mtp/head_loss"} <= found


def the_step_reports_the_modules_loss_test(built):
    """``Trainer.step``'s metrics: ``loss`` is ``L_main``, ``mtp_loss`` and
    ``mtp_loss_over_main`` ride beside it under ``telemetry_enabled``, and
    the module's sparse layer counts in the moe statistics."""
    config, _, _, batch, _ = built
    params = ModelParameter({**config, "telemetry_enabled": True})
    model = Model(params)
    trainer = Trainer(params, model)
    state = trainer.init_state(batch, seed=13)
    state, metrics = trainer.step(state, batch)
    assert float(metrics["loss"]) == float(metrics["token_loss"])
    assert 5.5 < float(metrics["mtp_loss"]) < 6.5     # ln(384) = 5.95
    assert float(metrics["mtp_loss_over_main"]) == pytest.approx(
        float(metrics["mtp_loss"]) / float(metrics["loss"]), rel=1e-5)
    from homebrewnlp_tpu.model import declare
    stats = declare.stats()
    assert (stats["mtp_loss"].metric, stats["mtp_loss"].fold) == (
        "hbnlp_mtp_loss", "mean")
    assert stats["mtp_loss_over_main"].metric == "hbnlp_mtp_loss_over_main"


def the_modules_layers_count_in_the_memory_rule_test():
    """The module's block pair are two more ``jax.checkpoint`` regions after
    the body's: its attention and its experts count as any other layer's in
    ``stash_plan`` (what ``hbnlp_remat_stash_layers{kind}`` and the ``remat
    stash:`` line publish), and without the module the counts are the
    body's."""
    # (a flash call engages at a sequence of whole 128-tiles)
    with_module = ModelParameter(_config(remat_policy="stash",
                                         sequence_length=128))
    without = ModelParameter(_config(remat_policy="stash", mtp_depth=0,
                                     sequence_length=128))
    assert remat.region_count(with_module) == 6
    assert remat.region_count(without) == 4
    plan, base = remat.stash_plan(with_module), remat.stash_plan(without)
    assert plan["attention"][0] == base["attention"][0] + 1 == 3
    assert plan["experts"][0] == base["experts"][0] + 1 == 3
    assert plan["attention"][1] * 2 == base["attention"][1] * 3
    assert len(remat.region_names(with_module)) == 6
    assert "attention 3 layers" in remat.stash_line(plan)


def two_passes_of_the_module_build_and_train_test():
    """``mtp_depth`` 2: the second pass joins the first's output to the token
    two on, has blocks of its own and is held to the token three on."""
    config, params, model, batch, variables = _build(mtp_depth=2)
    assert len(model.plan) == 4 + 4
    assert sum("mtp0/body1/block1_" in name for name in variables) \
        == sum("mtp0/body0/block0_" in name for name in variables) > 0
    info = harness.apply_with_stats(model, variables, batch)
    assert 5.5 < float(info.layer_stats["mtp_loss"][0]) < 6.5
    assert float(info.objective.data) > float(info.total_loss.data)


# ---- the repo's configuration ---------------------------------------------------

def the_repo_config_is_the_published_model_test():
    with open(os.path.join(REPO, "configs", "joyai_llm_flash.json")) as f:
        config = json.load(f)
    params = ModelParameter(config)
    assert not params.unknown_config_keys
    assert (params.heads * params.features_per_head, params.depth + 1,
            params.experts, params.moe_top_k, params.expert_width,
            params.vocab_size, params.mtp_depth, params.mtp_loss_weight,
            params.norm_epsilon, params.moe_route_scale) == (
        2048, 40, 256, 8, 768, 129280, 1, 0.3, 1e-6, 2.5)
    assert int(2048 * params.intermediate_feed_forward_multiplier) == 7168
    layer = ("attention-rope-theta32000000-q_heads32-kv_heads32-kv_latent512"
             "-shared_key64-q_latent1536")
    assert [b.layer[1] for b in params.input_block_config] == [layer,
                                                               "mlp-silu"]
    assert [b.layer[1] for b in params.block_config] \
        == [b.layer[1] for b in params.mtp_block_config] == [layer, MOE]


def the_cut_holds_the_issues_parameters_test():
    """The shapes the program builds at the cut, by part: ISSUE 65's table
    and ``benchmark/configs/joyai_llm_flash.json``'s count, 787,533,312."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "joyai_llm_flash.json")) as f:
        config = json.load(f)["config"]
    params = ModelParameter({**config, "sequence_length": 256})
    model = Model(params)
    tokens = np.zeros((1, 256, 1), np.int32)
    shapes: dict = {}
    real = scope.init_value
    scope.init_value = lambda init, name, seed, sizes, dtype: (
        shapes.__setitem__(name, tuple(sizes)),
        np.broadcast_to(np.zeros((), dtype), tuple(sizes)))[1]
    try:
        model.init({"token_x": tokens, "token_y": tokens}, seed=1)
    finally:
        scope.init_value = real

    def count(part):
        return sum(int(np.prod(s)) for n, s in shapes.items() if part in n)

    attention = 2048 * 1536 + 1536 + 1536 * 6144 + 2048 * 576 + 512 \
        + 512 * 8192 + 4096 * 2048
    assert attention == 26_347_520
    assert count("input0/lang_inp") == 70_391_808
    assert count("gpt0/body0/block0_") == 107_092_224
    assert count("/mtp0/") == 115_486_976
    assert count("gather0") + count("output0/embed0") == 66_191_360
    assert sum(int(np.prod(s)) for s in shapes.values()) == 787_533_312
