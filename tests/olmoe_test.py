"""OLMoE's layers through the normal path (ISSUE 26): the program against the
plain reference ``benchmark/reference/olmoe_1b_7b.py`` in logits, loss and
gradients at tiny widths, the dropless dispatch at its extreme, the chunked
head loss against the old one-hot form, and rotary positions' invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.model import loss as loss_mod, moe as moe_mod
from homebrewnlp_tpu.model.spatial import rotary

TINY = {"depth": 2, "heads": 4, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 384,
        "tpu_size": 1, "use_checkpointing": False, "slice_dtype": "float32"}


def _reference():
    return harness.reference("olmoe_1b_7b")


def _build(experts: int, top_k: int, dtype: str, **extra):
    return harness.build(harness.config_of(
        "olmoe_1b_7b", TINY, dtype, experts=experts, moe_top_k=top_k,
        **extra), data_seed=experts, init_seed=11)


# (16, 1): top-1 with every expert held (ZAYA1's count and choice, PR 39): one
# slot a token, against the dense loop over experts
CASES = [(8, 2), (64, 8), (16, 1)]


@pytest.mark.parametrize("experts,top_k", CASES)
@pytest.mark.parametrize("dtype,tolerance", [
    # float32 against float32: only the order of sums differs, so this pins
    # the EQUATIONS: a missing QK-norm, a renormalised top-k, a wrong rotary
    # convention or 1/sqrt(width) are off by orders of magnitude more
    ("float32", 2e-5),
    # the configuration's bfloat16: activations, residual stream and logits
    # carry 8 bits of mantissa (0.4% each), and where that rounding moves
    # the router's k-th and (k+1)-th probabilities past each other a token
    # changes by one expert's weight times the difference of two experts'
    # outputs.  2^-4 is the chip runs' bound (the cell's logit_tolerance);
    # float8 activations land far above it
    # (reference_at_the_next_precision_below_fails_test)
    ("bfloat16", 2 ** -4)])
def program_matches_reference_test(experts, top_k, dtype, tolerance):
    # the loss is reported in the calculation dtype: bfloat16's spacing
    # between 4 and 8 is 2^-5, float32 sums 128 terms
    got = harness.assert_program_matches_reference(
        _reference(), _build(experts, top_k, dtype), dtype, tolerance)
    assert got.shape == (2, 64, 384)


@pytest.mark.parametrize("experts,top_k", CASES)
def loss_and_gradients_match_reference_test(experts, top_k):
    """Value and every parameter's gradient against ``jax.grad`` of the
    reference's ``train_loss``: cross-entropy plus BOTH router terms, which
    the program's step injects into the router's cotangent and never
    reports."""
    config, params, model, batch, variables = _build(experts, top_k,
                                                     "float32")
    ref = _reference()
    tokens, targets = batch["token_x"][..., 0], batch["token_y"][..., 0]
    variables = {k: jnp.asarray(v) for k, v in variables.items()}
    assert params.train and params.moe_balance_loss and \
        params.moe_router_z_loss
    got_loss, got = harness.loss_and_grads(model, variables, batch)
    _, want = harness.reference_loss_and_grads(ref, variables, tokens,
                                               targets, config)
    from benchmark.reference import common
    want_loss = common.loss_of(ref.forward(variables, tokens, config),
                               targets, 0.0)
    assert abs(float(got_loss) - float(want_loss)) <= 2.0 ** -18
    # float32 both sides; sums of up to 128 tokens x 64 features in another
    # order, and a softmax's gradient through exp: 1e-4 of the parameter's
    # largest gradient
    harness.assert_grads_match(got, want, 1e-4, alive=True)
    # the router terms are in those gradients: without them the router's
    # differ by far more than the tolerance
    plain = jax.jit(jax.grad(lambda v: common.loss_of(
        ref.forward(v, tokens, config), targets, 0.0)))(variables)
    router = "gpt0/body0/block0_1_0/moe_0/normal_var0/var0"
    assert float(jnp.max(jnp.abs(plain[router] - want[router]))) \
        > 1e-2 * float(jnp.max(jnp.abs(want[router])))


def reference_at_the_next_precision_below_fails_test():
    """The bound on the chip (2^-4 of the largest logit) is between the two
    readings it was set from: bfloat16 passes it (above), and the same
    program with its activations rounded to float8 (e4m3, 3 bits of
    mantissa) does not."""
    config, params, model, batch, variables = _build(64, 8, "float32")
    want = np.asarray(_reference().forward(
        variables, batch["token_x"][..., 0], config))
    lowered = {k: np.asarray(jnp.asarray(v).astype(jnp.float8_e4m3fn)
                             .astype(jnp.float32))
               for k, v in variables.items()}
    got = np.asarray(_reference().forward(
        lowered, batch["token_x"][..., 0], config))
    err = np.max(np.abs(want - got)) / np.max(np.abs(want))
    assert err > 2 ** -4, err


def every_pair_reaches_an_expert_test():
    """One expert takes ALL tokens (and a second all their other choice):
    nothing is dropped, nothing padded; the grouped matmul's rows are each
    pair's token times ITS expert's matrix."""
    t, f, n, e, k = 96, 8, 5, 6, 2
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(t, e)).astype(np.float32) * 0.1
                         + np.array([9.0, 0, 0, 6.0, 0, 0], np.float32))
    weights, experts = moe_mod.route(logits, k)
    assert np.all(np.asarray(experts) == [0, 3])
    order, inverse, sizes = moe_mod.sort_pairs(experts, e)
    assert np.asarray(sizes).tolist() == [t, 0, 0, t, 0, 0]
    assert sorted(np.asarray(order).tolist()) == list(range(t * k))
    assert np.all(np.asarray(order)[np.asarray(inverse)] == np.arange(t * k))
    x = jnp.asarray(rng.normal(size=(t, f)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(e, f, n)).astype(np.float32))
    rows = moe_mod._dispatch(x, order, inverse, k)
    out = moe_mod.grouped_dot(rows, w, sizes)
    per_pair = np.einsum("tf,tkfn->tkn", np.asarray(x),
                         np.asarray(w)[np.asarray(experts)])
    np.testing.assert_allclose(np.asarray(out)[np.asarray(inverse)],
                               per_pair.reshape(t * k, n), rtol=1e-5,
                               atol=1e-5)
    got = moe_mod._combine(out, weights, order, inverse, k)
    want = np.einsum("tkn,tk->tn", per_pair, np.asarray(weights))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)

    # dispatch's and combine's hand-written backward passes (gathers both
    # ways) against autodiff of the plain indexed forms
    def layer(x, w, weights, plain):
        if plain:
            rows = x[order // k]
            y = jax.lax.ragged_dot(rows, w, sizes)[inverse].reshape(t, k, n)
            return jnp.sum(jnp.sin(jnp.sum(y * weights[..., None], axis=1)))
        y = moe_mod.grouped_dot(moe_mod._dispatch(x, order, inverse, k), w,
                                sizes)
        return jnp.sum(jnp.sin(moe_mod._combine(y, weights, order, inverse,
                                                 k)))

    for got, want in zip(
            jax.grad(layer, argnums=(0, 1, 2))(x, w, weights, False),
            jax.grad(layer, argnums=(0, 1, 2))(x, w, weights, True)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-5)


def the_step_reports_the_routed_layers_load_test():
    """With a zero router every probability ties and the first ``k`` experts
    take every token: load max/mean = experts / k in both layers, all pairs
    routed; reported only when asked (the trainer asks under
    ``telemetry_enabled``)."""
    config, params, model, batch, variables = _build(8, 2, "float32")
    zeroed = {k: (np.zeros_like(v) if "moe_0/normal_var0" in k else v)
              for k, v in variables.items()}
    info = harness.apply_with_stats(model, zeroed, batch)
    np.testing.assert_allclose(
        np.asarray(info.layer_stats["moe_load_max_over_mean"]), [4.0, 4.0])
    np.testing.assert_allclose(
        np.asarray(info.layer_stats["moe_routed_pairs"]), [256.0, 256.0])
    assert jax.eval_shape(lambda v: model.apply(v, batch),
                          zeroed).layer_stats is None
    grads = jax.jit(jax.grad(lambda v: model.apply(
        {k: jnp.asarray(a) for k, a in v.items()}, batch,
        layer_stats=True).total_loss.data))(zeroed)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in grads.values())


def _count_primitives(jaxpr, prefixes):
    """Equations whose primitive's name starts with each prefix, through
    every nested jaxpr (a scan's body counts once)."""
    counts = dict.fromkeys(prefixes, 0)
    for eqn in jaxpr.eqns:
        for prefix in prefixes:
            counts[prefix] += eqn.primitive.name.startswith(prefix)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    for k, n in _count_primitives(sub, prefixes).items():
                        counts[k] += n
    return counts


@pytest.mark.parametrize("scan", [False, True])
def saved_expert_outputs_change_no_bit_test(scan):
    """The experts kind (model/remat.py; PR 29) against ``remat_policy:
    "recompute"``: the same loss and the same gradients, bit for bit — the
    backward reads the forward's own gate / up / down outputs and routing
    triple instead of equal recomputed ones — with three grouped matmuls
    and one sort a layer fewer in the gradient's program."""
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.train import Trainer
    results, counts = {}, {}
    for policy in ("auto", "recompute"):
        _, params, model, batch, variables = _build(
            8, 2, "float32", remat_policy=policy, scan_layers=scan)
        variables = {k: jnp.asarray(v) for k, v in variables.items()}
        step = jax.value_and_grad(
            lambda v: model.apply(v, batch).total_loss.data)
        counts[policy] = _count_primitives(
            jax.make_jaxpr(step)(variables).jaxpr, ("ragged_dot", "sort"))
        results[policy] = jax.jit(step)(variables)
        # 256 pairs x (2 x 32 + 64) x float32 + (2 x 256 + 8) x int32 and
        # (PR 39) the router's choice [128, 2] x int32, twice
        engaged = (2, 268352) if policy == "auto" else (0, 0)
        line = Trainer(params, model).publish_stash_plan()
        assert line.endswith(f"experts {engaged[0]} layers, {engaged[1]} "
                             "bytes a device; recurrent 0 layers, 0 bytes a "
                             "device; dense 0 layers, 0 bytes a device")
        snap = telemetry.registry().snapshot()
        assert snap["hbnlp_remat_stash_layers"]["series"][("experts",)] \
            == engaged[0]
        assert snap["hbnlp_remat_stash_bytes"]["series"][("experts",)] \
            == engaged[1]
    (loss, grads), (want_loss, want) = results["auto"], results["recompute"]
    assert float(loss) == float(want_loss)
    assert set(grads) == set(want)
    for name in sorted(want):
        assert np.array_equal(np.asarray(grads[name]),
                              np.asarray(want[name])), name
    # a scanned body is one jaxpr whatever the depth
    layers = 1 if scan else params.depth
    # forward 3, replay 3, input and weight gradients 6 a layer: 12 -> 9
    assert counts["recompute"]["ragged_dot"] == 12 * layers
    assert counts["auto"]["ragged_dot"] == 9 * layers
    assert counts["recompute"]["sort"] - counts["auto"]["sort"] == layers


@pytest.mark.parametrize("policy", ["auto", "recompute"])
def every_expert_held_is_the_parents_step_test(policy):
    """A layer that holds every expert bypasses the held path's tiled passes
    (ISSUE 47): the value-and-gradient program of this file's tiny model
    traces to the pinned jaxpr, source positions and addresses stripped, with
    the experts' outputs saved and with everything replayed."""
    import re
    _, params, model, batch, variables = _build(8, 2, "bfloat16",
                                                remat_policy=policy)
    step = jax.value_and_grad(lambda v: model.apply(v, batch).total_loss.data)
    text = re.sub(r" at \S+:\d+", "", str(jax.make_jaxpr(step)(variables)))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert "moe_inverse" in text and "while" not in text
    harness.pinned("step/olmoe_toy/every_expert_held/" + policy, text)


def _old_cross_entropy(logits, targets, z_loss):
    """model/__init__.py's form before ISSUE 26: max-subtracted log-softmax
    against a one-hot of the targets."""
    top = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    log_z = jnp.log(jnp.sum(jnp.exp(logits - top), axis=-1,
                            keepdims=True)) + top
    hot = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
    loss = -jnp.sum((logits - log_z) * hot) / targets.size
    return loss + z_loss * jnp.sum(log_z * log_z) / targets.size


@pytest.mark.parametrize("chunk_bytes", [1 << 29, 1 << 12])
def cross_entropy_is_the_old_one_test(chunk_bytes, monkeypatch):
    """Value and gradient at vocabulary 256, from logits and fused with the
    head matmul, in one chunk and in eight."""
    # the OLMoE cell's head: 4 chunks of 1,024 positions x 2 sequences
    assert loss_mod.chunks_for(2, 4096, 1, 50304) == 4
    monkeypatch.setattr(loss_mod, "CHUNK_BYTES", chunk_bytes)
    b, s, h, k, p, v = 2, 64, 2, 8, 1, 256
    assert loss_mod.chunks_for(b, s, p, v) == (1 if chunk_bytes > 1 << 20
                                              else 32)

    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(b, s, h, k)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(h, k, p, v)).astype(np.float32))
    targets = jnp.asarray(rng.integers(0, v, (b, s, p)).astype(np.int32))
    z = 1e-2

    def old(x, w):
        return _old_cross_entropy(jnp.einsum("bshk,hkpv->bspv", x, w),
                                  targets, z)

    want, (want_x, want_w) = jax.value_and_grad(old, argnums=(0, 1))(x, w)
    got, (got_x, got_w) = jax.value_and_grad(
        lambda x, w: loss_mod.head_xent(x, w, targets, z),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(got_x), np.asarray(want_x),
                               rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(np.asarray(got_w), np.asarray(want_w),
                               rtol=1e-4, atol=1e-7)
    logits = jnp.einsum("bshk,hkpv->bspv", x, w)
    want_l = jax.grad(lambda l: _old_cross_entropy(l, targets, z))(logits)
    got_v, got_l = jax.value_and_grad(
        lambda l: loss_mod.head_xent(l, None, targets, z))(logits)
    np.testing.assert_allclose(float(got_v), float(want), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(got_l), np.asarray(want_l),
                               rtol=1e-4, atol=1e-9)
    # forward only (no gradient asked): the same value
    np.testing.assert_allclose(
        float(loss_mod.head_xent(x, w, targets, z)), float(want), rtol=2e-6)


def rotary_depends_on_the_relative_position_test():
    """``rope(q)_i . rope(k)_j`` is a function of ``i - j``; position 0 is
    untouched, norms are kept, and the convention is HF's rotate-half (the
    reference's)."""
    rng = np.random.default_rng(5)
    q = np.tile(rng.normal(size=(1, 1, 2, 32)).astype(np.float32),
                (1, 48, 1, 1))
    k = np.tile(rng.normal(size=(1, 1, 2, 32)).astype(np.float32),
                (1, 48, 1, 1))
    rq, rk = rotary(jnp.asarray(q), 10000.0), rotary(jnp.asarray(k), 10000.0)
    scores = np.asarray(jnp.einsum("bshd,bthd->bhst", rq, rk))[0]
    for shift in (1, 7, 30):
        diagonal = scores[:, shift:, :-shift].diagonal(axis1=1, axis2=2)
        np.testing.assert_allclose(
            diagonal, np.broadcast_to(scores[:, shift, :1], diagonal.shape),
            rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(rq)[:, 0], q[:, 0], rtol=1e-6)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(rq), axis=-1),
                               np.linalg.norm(q, axis=-1), rtol=1e-5)
    x = rng.normal(size=(2, 48, 2, 32)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(rotary(jnp.asarray(x), 10000.0)),
        np.asarray(_reference().rope(jnp.asarray(x), 10000.0)),
        rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("path,scope", [
    ("jit(step_fn)/gpt0/body0/block1_1_0/moe_0/experts/gmm", "body/moe/experts"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/checkpoint/block0_1_0/moe_0/"
     "transpose(jvp(dispatch))/gather", "body/moe/dispatch"),
    ("gpt0/body0/block0_1_0/moe_0/router/dot_general", "body/moe/router"),
    ("gpt0/body0/block0_1_0/moe_0/combine/reduce_sum", "body/moe/combine"),
    ("gpt0/body0/block0_1_0/moe_0/normal_var0/convert", "body/moe"),
    ("gpt0/body0/block0_0_0/attention_0/rope/mul", "body/attention"),
    ("gpt0/body0/block0_0_0/norm_0/rsqrt", "body/norm"),
    ("jit(step_fn)/jvp(gpt0)/loss0/head_loss/dot_general", "head_loss"),
    ("gpt0/output0/lang_out0_0/norm_0/mul", "output"),
    ("gpt0/body0/block0_1_0/norm_0/experts/x", "body/norm")])
def the_new_scopes_fold_test(path, scope):
    assert scope_key(path) == scope
