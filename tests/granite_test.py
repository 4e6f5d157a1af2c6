"""granite-4.0-h-micro's layers through the normal path (ISSUE 30): the
chunked scan against the recurrence run position by position, the causal
conv, grouped-query flash attention, the tied head's gradient, the program
against the plain reference ``benchmark/reference/granite_4_0_h_micro.py``,
the twenty-block period, and the new scopes and gauges."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from harness import REPO
from homebrewnlp_tpu.analysis.cost_ledger import scope_key
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import mamba as mamba_mod
from homebrewnlp_tpu.parallel import flash_attention as fa

TINY = {"depth": 1, "heads": 4, "features_per_head": 16,
        "sequence_length": 64, "train_batch_size": 2, "vocab_size": 384,
        "mamba_heads": 4, "mamba_head_features": 8, "mamba_state": 16,
        "mamba_chunk": 16, "tpu_size": 1, "use_checkpointing": False}


CELL = "train_granite_4_0_h_micro_long"
#: one short period that holds each layer kind of the published twenty
#: blocks, for what is not about the pattern (a step's compile is the
#: blocks' count: PR 60)
SHORT = [{"skip": True, "layer": ["norm-rms-scale", layer]} for layer in (
    "mamba", "mlp-silu", "attention-nope", "mlp-silu", "mamba")]


def _reference():
    return harness.reference("granite_4_0_h_micro")


def _config(dtype: str = "float32", **extra) -> dict:
    return harness.config_of("granite_4_0_h_micro", TINY, dtype, **extra)


def _build(dtype: str = "float32", **extra):
    return harness.build(_config(dtype, **extra))


# ---- the chunked scan --------------------------------------------------------

def _scan_inputs(s: int, decay: float, seed: int = 0):
    """``dt * a`` per position is about ``-decay``: at 6 a position a product
    of 16 exponentials is e^-96, below float32's smallest normal number."""
    rng = np.random.default_rng(seed)
    b, h, p, n = 2, 3, 4, 5
    x = rng.normal(size=(b, s, h, p)).astype(np.float32)
    dt = rng.uniform(0.5, 1.5, size=(b, s, h)).astype(np.float32)
    a = -decay * rng.uniform(0.5, 1.5, size=(h,)).astype(np.float32)
    b_mat = rng.normal(size=(b, s, n)).astype(np.float32)
    c_mat = rng.normal(size=(b, s, n)).astype(np.float32)
    return tuple(jnp.asarray(t) for t in (x, dt, a, b_mat, c_mat))


@pytest.mark.parametrize("decay", [0.05, 6.0])
@pytest.mark.parametrize("chunk,s", [(8, 8), (8, 32), (16, 16), (16, 64)])
def chunked_scan_is_the_recurrence_test(chunk, s, decay):
    """Values and all five gradients, one chunk and several, with decays so
    strong that any product (or quotient) of exponentials along a chunk
    under- (or over-)flows: the scan forms ``exp`` of differences only."""
    inputs = _scan_inputs(s, decay)
    weights = jnp.asarray(np.random.default_rng(1).normal(
        size=inputs[0].shape).astype(np.float32))
    recurrence = _reference().recurrence

    def chunked(*args):
        return jnp.sum(mamba_mod.ssd(*args, chunk)[0] * weights)

    def plain(*args):
        return jnp.sum(recurrence(*args) * weights)

    got = jax.jit(lambda *a: mamba_mod.ssd(*a, chunk)[0])(*inputs)
    want = recurrence(*inputs)
    assert np.all(np.isfinite(np.asarray(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    got_g = jax.jit(jax.grad(chunked, argnums=(0, 1, 2, 3, 4)))(*inputs)
    # the reference loops with fori_loop, which reverse mode cannot cross:
    # the same recurrence under scan's rule
    want_g = jax.jit(jax.grad(
        lambda *a: jnp.sum(_scan_recurrence(*a) * weights),
        argnums=(0, 1, 2, 3, 4)))(*inputs)
    np.testing.assert_allclose(
        float(plain(*inputs)), float(jax.jit(lambda *a: jnp.sum(
            _scan_recurrence(*a) * weights))(*inputs)), rtol=1e-5)
    for g, w in zip(got_g, want_g):
        assert np.all(np.isfinite(np.asarray(g)))
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)


def _scan_recurrence(x, dt, a, b_mat, c_mat):
    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = state * jnp.exp(dt_t * a)[..., None, None] \
            + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :]
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t)

    b, _, h, p = x.shape
    init = jnp.zeros((b, h, p, b_mat.shape[-1]), jnp.float32)
    _, ys = jax.lax.scan(step, init, tuple(
        jnp.moveaxis(t, 1, 0) for t in (x, dt, b_mat, c_mat)))
    return jnp.moveaxis(ys, 0, 1)


def log_decay_watch_test():
    """The scan's second output is the most negative cumulative ``dt a``
    inside a chunk."""
    x, dt, a, b_mat, c_mat = _scan_inputs(32, 12.0)
    _, low = mamba_mod.ssd(x, dt, a, b_mat, c_mat, 8)
    per_chunk = np.asarray(dt * a).reshape(2, 4, 8, 3).sum(axis=2)
    np.testing.assert_allclose(float(low), per_chunk.min(), rtol=1e-6)
    assert float(low) < -87      # exp of it is 0 in float32, and no harm


@pytest.mark.parametrize("width", [1, 4])
def conv_is_shifted_multiplies_test(width):
    rng = np.random.default_rng(2)
    x = rng.normal(size=(2, 10, 6)).astype(np.float32)
    w = rng.normal(size=(width, 6)).astype(np.float32)
    bias = rng.normal(size=(6,)).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(10):
        for k in range(width):
            src = t - (width - 1) + k
            if src >= 0:
                want[:, t] += w[k] * x[:, src]
    want += bias
    got = mamba_mod.causal_depthwise_conv(jnp.asarray(x), jnp.asarray(w),
                                          jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-6)
    # the reference's own four shifted multiplies agree
    np.testing.assert_allclose(
        np.asarray(_reference()._conv(jnp.asarray(x), jnp.asarray(w),
                                      jnp.asarray(bias))), want,
        rtol=1e-5, atol=1e-6)


# ---- grouped-query flash attention -------------------------------------------

@pytest.mark.parametrize("form", fa.BACKWARD_FORMS)
@pytest.mark.parametrize("heads,kv_heads", [(8, 2), (4, 4), (4, 1)])
def grouped_flash_matches_repeated_reference_test(heads, kv_heads, form,
                                                  monkeypatch):
    """Grouped queries as the standard attention runs them — K and V
    repeated over their group, then the multi-head kernels (interpret mode),
    autodiff summing dk and dv over the group: forward, dQ, dK, dV against
    the dense form, each form of the backward."""
    monkeypatch.setattr(fa, "backward_form", lambda *a: form)
    rng = np.random.default_rng(heads * 10 + kv_heads)
    b, s, d = 2, 256, 32
    q, do = (jnp.asarray(rng.normal(size=(b, s, heads, d)).astype(np.float32))
             for _ in range(2))
    k, v = (jnp.asarray(rng.normal(size=(b, s, kv_heads, d))
                        .astype(np.float32)) for _ in range(2))
    group = heads // kv_heads

    def grouped(attend):
        return lambda q, k, v: attend(q, jnp.repeat(k, group, axis=2),
                                      jnp.repeat(v, group, axis=2))

    kernel = grouped(lambda q, k, v: fa.flash_attention(
        q, k, v, 0.2, True, 64, 128, True, 64, 64))
    dense = grouped(lambda q, k, v: fa._xla_reference(q, k, v, 0.2, True))
    want_out, *want = harness.with_input_grads(dense, (q, k, v), do)
    np.testing.assert_allclose(np.asarray(kernel(q, k, v)),
                               np.asarray(want_out), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernel(*a) * do), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


def grouped_attention_layer_matches_its_equation_test():
    """The layer itself at ``query_group`` 4: its output is ``softmax(scale
    q k_j^T) v_j Wo`` with K/V head ``j = i // 4`` for query head ``i``."""
    _, params, model, batch, variables = _build("float32")
    from homebrewnlp_tpu.core import scope
    from homebrewnlp_tpu.core.tensor import nt
    from homebrewnlp_tpu.config import BlockArgs
    from homebrewnlp_tpu.model.spatial import attention
    prefix = "gpt0/body0/block0_10_0/attention_0/normal_var"
    w_k, w_q, w_v, w_o = (jnp.asarray(variables[f"{prefix}{i}/var0"])
                          for i in range(4))
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 64, 4, 16)).astype(np.float32))
    ctx = scope.Context("apply", params={
        k.replace("gpt0/body0/block0_10_0/", ""): v
        for k, v in variables.items() if "block0_10_0/attention" in k})
    with scope.context(ctx):
        params.attention_idx = 0
        got = scope.scoped("attention_", attention, BlockArgs(
            params, nt(x, [params.batch_dim, params.sequence_dim]
                       + list(params.feature_dims)), ["nope"])).data
    q = jnp.einsum("bsgf,gfhd->bshd", x, w_q)
    k = jnp.einsum("bsgf,gfhd->bshd", x, w_k)
    v = jnp.einsum("bsgf,gfhd->bshd", x, w_v)
    assert k.shape[2] == 1 and q.shape[2] == 4
    score = jnp.einsum("bshd,btd->bhst", q, k[:, :, 0]) * params.attention_scale
    causal = jnp.arange(64)[:, None] >= jnp.arange(64)[None, :]
    weight = jax.nn.softmax(jnp.where(causal, score, -jnp.inf), axis=-1)
    want = jnp.einsum("bsgf,gfhd->bshd",
                      jnp.einsum("bhst,btd->bshd", weight, v[:, :, 0]), w_o)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---- the model ---------------------------------------------------------------

@pytest.mark.parametrize("dtype,tolerance", [
    # float32 against float32: only the order of sums differs, so this pins
    # the EQUATIONS (gate before norm, the conv's alignment, the softmax
    # scale, the three multipliers, the tied head) and the chunked scan
    ("float32", 2e-5),
    # the configuration's bfloat16 at the chip runs' bound
    ("bfloat16", 2 ** -5)])
def program_matches_reference_test(dtype, tolerance):
    got = harness.assert_program_matches_reference(
        _reference(), _build(dtype), dtype, tolerance)
    assert got.shape == (2, 64, 384)


def reference_at_the_next_precision_below_fails_test():
    """The bound on the chip (2^-5 of the largest logit) is between the two
    readings it was set from: bfloat16 passes it (above, and as a stream
    here), and the reference with its residual stream rounded to float8
    (e4m3, 3 bits of mantissa) after every block does not."""
    config, params, model, batch, variables = _build("float32")
    ref, tokens = _reference(), batch["token_x"][..., 0]
    want = ref.forward(variables, tokens, config)
    errs = {}
    for dtype in (jnp.bfloat16, jnp.float8_e4m3fn):
        got = ref.forward(variables, tokens, config, stream_dtype=dtype)
        errs[dtype] = np.max(np.abs(want - got)) / np.max(np.abs(want))
    assert errs[jnp.bfloat16] < 2 ** -5 < errs[jnp.float8_e4m3fn], errs


def tied_head_gradient_is_the_sum_of_both_uses_test():
    """One parameter, read by the gather and by the head: its gradient is
    the untied twin's embedding gradient plus its head gradient."""
    _, _, tied, batch, variables = _build("float32", block_config=SHORT)
    _, _, untied, _, twin = _build("float32", block_config=SHORT,
                                   tie_word_embeddings=False)
    table = "gpt0/input0/gather0/embed0/normal_var0/var0"
    head = "gpt0/output0/embed0/normal_var0/var0"
    assert head not in variables and set(twin) == set(variables) | {head}
    twin = dict(variables, **{head: np.transpose(
        np.asarray(variables[table]), (1, 2, 0))[:, :, None, :]})

    loss, got = harness.loss_and_grads(tied, variables, batch)
    want_loss, want = harness.loss_and_grads(untied, twin, batch)
    assert abs(float(loss) - float(want_loss)) < 1e-6
    both = np.asarray(want[table]) + np.transpose(
        np.asarray(want[head])[:, :, 0, :], (2, 0, 1))
    assert np.max(np.abs(np.asarray(want[head]))) > 0
    np.testing.assert_allclose(np.asarray(got[table]), both,
                               rtol=1e-4, atol=1e-7)
    for name in set(got) - {table}:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), rtol=1e-4,
                                   atol=1e-7)


def twenty_blocks_at_depth_two_are_forty_scopes_test():
    config, params, model, _, variables = _build("float32", depth=2)
    assert len(params.block_config) == 20
    scopes = sorted({name.split("/")[2] for name in variables
                     if "/body0/" in name})
    assert len(scopes) == 40 == len(model.plan)
    mixers = [next(n.split("/")[3] for n in variables
                   if f"/block{d}_{c}_0/" in n and "/norm_" not in n)
              for d in range(2) for c in range(0, 20, 2)]
    assert mixers == (["mamba_0"] * 5 + ["attention_0"] + ["mamba_0"] * 4) * 2
    assert all(any(f"/block{d}_{c}_0/mlp_0/" in n for n in variables)
               for d in range(2) for c in range(1, 20, 2))
    # 9 Mamba layers of 8 parameters, one attention of 4, ten MLPs of 3,
    # twenty norms, a depth; the embedding (tied: no head) and the last norm
    assert len(variables) == 2 * (9 * 8 + 4 + 10 * 3 + 20) + 2
    # grouped heads: key and value project to heads / query_group heads
    key = variables["gpt0/body0/block0_10_0/attention_0/normal_var0/var0"]
    assert key.shape == (4, 16, 1, 16)


def published_configuration_counts_test():
    """The repository's full-depth configuration: 3.19 B parameters, every
    width as published."""
    with open(os.path.join(REPO, "configs", "granite_4_0_h_micro.json")) as f:
        config = json.load(f)
    params = ModelParameter(dict(config, model_path="/tmp/granite_counts"))
    assert not params.unknown_config_keys
    d = params.heads * params.features_per_head
    assert (d, params.intermediate[0].size, params.vocab_size) \
        == (2048, 8192, 100352)
    inner = params.mamba_heads * params.mamba_head_features
    mamba_layer = d * (2 * inner + 2 * params.mamba_state + params.mamba_heads) \
        + (params.mamba_conv_size + 1) * (inner + 2 * params.mamba_state) \
        + 3 * params.mamba_heads + inner + inner * d
    attention_layer = 2 * d * d + 2 * d * d // params.query_group
    mlp = 3 * d * 8192 + d                       # and its norm
    period = 9 * (mamba_layer + d) + attention_layer + d + 10 * mlp
    assert period == 746_468_288
    assert 4 * period + d * 100352 + d == 3_191_396_096


@pytest.mark.parametrize("bad,message", [
    ({"query_group": 3}, "query_group"),
    ({"vocab_weight_factorization": 0.125}, "tie_word_embeddings"),
    ({"memory_reduction_strategy": "revnet"}, "residual_multiplier")])
def configuration_is_validated_test(bad, message):
    with pytest.raises(ValueError, match=message):
        ModelParameter(dict(_config(), **bad))


def decode_forms_are_later_issues_test():
    _, params, model, batch, variables = _build("float32")
    with pytest.raises(NotImplementedError, match="decode"):
        model.apply_decode(variables, batch["token_x"][:, :1],
                           jnp.int32(0), {})


# ---- scopes, gauges, the memory strategy -------------------------------------

@pytest.mark.parametrize("path,scope", [
    ("gpt0/body0/block0_0_0/mamba_0/ssd/intra_chunk/dot_general",
     "body/mamba/ssd"),
    ("jit(step_fn)/transpose(jvp(gpt0))/body0/checkpoint/block0_4_0/"
     "mamba_0/ssd/inter_chunk/while", "body/mamba/ssd"),
    ("gpt0/body0/block0_2_0/mamba_0/in_proj/dot_general",
     "body/mamba/in_proj"),
    ("gpt0/body0/block0_2_0/mamba_0/gate_norm/mul", "body/mamba/gate_norm"),
    ("gpt0/body0/block0_2_0/mamba_0/normal_var0/convert", "body/mamba"),
    ("gpt0/body0/block0_3_0/mlp_0/silu/mul", "body/mlp"),
    ("gpt0/body0/block0_10_0/attention_0/flash_attention/pallas_call",
     "body/attention")])
def new_layers_fold_into_their_scopes_test(path, scope):
    assert scope_key(path) == scope


def _mamba_layer_hlo(v5e, monkeypatch) -> str:
    """One ``mamba`` layer at the published widths, 1 x 8,192 tokens, loss
    and gradients compiled for a v5e as a TPU process traces them (once a
    module: both kernel tests read the same text)."""
    from homebrewnlp_tpu.model import recurrent
    params, hlo = harness.cell_layer_hlo(v5e, monkeypatch, CELL, 0)
    assert params.block_config[0].layer[-1] == "mamba"
    assert params.sequence_length == 8192
    assert recurrent.conv_kernel_layers(params) == 1
    assert recurrent.scan_kernel_layers(params) == 1
    return hlo


def _kernel_calls(hlo: str, prefix: str):
    """``(name, op_name)`` of the Pallas calls named ``prefix*``, each in
    its three forms: forward, ``checkpoint``'s replay, backward."""
    calls = [call for call in harness.kernel_calls(hlo)
             if call[0].startswith(prefix)]
    assert sorted(name for name, _ in calls) \
        == [prefix + "bwd", prefix + "fwd", prefix + "fwd"]
    forms = sorted(op_name for _, op_name in calls)
    assert forms[0].split("/")[1].startswith("jvp(")
    assert "rematted_computation" in forms[2] and prefix + "fwd" in forms[2]
    assert forms[1].split("/")[1].startswith("transpose(jvp(") \
        and prefix + "bwd" in forms[1]
    return calls


def conv_kernels_keep_their_scope_and_names_test(v5e, monkeypatch):
    """The conv is the Pallas pair in its three forms (forward,
    ``checkpoint``'s replay, backward), Mosaic accepts both kernels, every
    one folds into ``body/mamba/conv`` and none bears a name another metric's
    reader takes (``^flash_``, ``^map_mixer_``)."""
    import re
    for name, op_name in _kernel_calls(_mamba_layer_hlo(v5e, monkeypatch),
                                       "mamba_conv_"):
        assert scope_key(op_name) == "body/mamba/conv", op_name
        assert not re.match(r"flash_|map_mixer_", name)


def scan_kernels_keep_their_scope_and_names_test(v5e, monkeypatch):
    """PR 48: the chunked scan is the Pallas pair ``ssd_scan_fwd`` /
    ``ssd_scan_bwd`` in the same three forms, Mosaic accepts both at the
    cell's shapes, every one folds into ``body/mamba/ssd`` (what both
    ``ssd_scan_*`` readers take), and no op of that scope outside the Pallas
    calls is shaped ``[.., 256, 256]``: the decay matrices stay in VMEM."""
    import re
    hlo = _mamba_layer_hlo(v5e, monkeypatch)
    for _, op_name in _kernel_calls(hlo, "ssd_scan_"):
        assert scope_key(op_name) == "body/mamba/ssd", op_name
    in_scope = [line for line in hlo.splitlines()
                if (m := re.search(r'op_name="([^"]+)"', line))
                and scope_key(m.group(1)) == "body/mamba/ssd"
                and "tpu_custom_call" not in line]
    assert in_scope
    for line in in_scope:
        assert not re.search(r"\[[\d,]*256,256\]", line.split(" = ")[1]
                             .split("(")[0]), line


def saved_flash_outputs_keep_their_scope_test(v5e, monkeypatch):
    """The cell's ``attention-nope`` layer (``harness.py
    saved_flash_outputs_keep_their_scope``)."""
    harness.saved_flash_outputs_keep_their_scope(
        v5e, monkeypatch, CELL, "attention-nope", "body/attention")


def experts_rule_declines_without_a_moe_layer_test():
    """``checkpoint`` with no ``moe`` layer: the experts kind does not ride;
    the recurrent kind does (PR 71: layer ``mamba``'s in-projection outputs),
    so every region's policy saves that name, and the chunk states' gauge
    counts one layer's."""
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.model import recurrent, remat
    from homebrewnlp_tpu.model.blocks import _checkpoint_policy
    from homebrewnlp_tpu.train import Trainer
    _, params, model, _, _ = _build("float32")
    assert params.memory_reduction_strategy == "checkpoint"
    assert remat.stash_plan(params)["experts"] == (0, 0)
    assert "experts" not in remat.stash_kinds(params)
    # layer mamba offers its in-projection's output (PR 71; nothing until
    # then): the nine toy ones [2, 64, 2 x 32 + 2 x 16 + 4] float32 fit
    assert remat.stash_plan(params)["recurrent"] == (9, 9 * 2 * 64 * 100 * 4)
    assert "recurrent" in remat.stash_kinds(params)
    assert remat.stash_names(params)[0] == "mamba_in_proj"
    assert _checkpoint_policy(params) \
        is not jax.checkpoint_policies.nothing_saveable
    # [2, 64 / 16, 4, 8, 16] float32
    assert recurrent.ssd_state_bytes(params) == 2 * 4 * 4 * 8 * 16 * 4
    line = Trainer(params, model).publish_stash_plan()
    # (PR 52: the ten toy MLPs' gate and up [2, 64, 256] float32 fit, and
    # ride the regions' own policies: tests/remat_policy_test.py)
    assert line.endswith("experts 0 layers, 0 bytes a device; recurrent 9 "
                         "layers, 460800 bytes a device; dense 10 layers, "
                         "2621440 bytes a device; ssd chunk states 16384 "
                         "bytes a device; conv kernel 0 layers; scan kernel "
                         "0 layers")
    snap = telemetry.registry().snapshot()
    assert snap["hbnlp_ssd_state_bytes"]["series"][()] == 16384
    assert snap["hbnlp_mamba_conv_kernel_layers"]["series"][()] == 0
    assert snap["hbnlp_ssd_scan_kernel_layers"]["series"][()] == 0
    _, none, _, _, _ = _build("float32", memory_reduction_strategy="none")
    assert recurrent.ssd_state_bytes(none) == 9 * 16384


def _replayed_in_projections(jaxpr) -> list:
    """The matmuls of the in-projection's shape — ``[2, 64, 64] x [64, 100]
    -> [2, 64, 100]`` — in the BACKWARD of every ``jax.checkpoint`` region of
    a gradient's jaxpr (where the region's replay is), in execution order of
    the regions: the backward holds them last region first."""
    counts = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name != "remat2":
            continue
        counts.append(sum(
            e.primitive.name == "dot_general"
            and tuple(e.outvars[0].aval.shape) == (2, 64, 100)
            for e in eqn.params["jaxpr"].eqns))
    return counts[::-1]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def saved_in_projection_changes_no_value_test(dtype):
    """PR 71: a ``checkpoint`` step of mamba blocks with the in-projection's
    output saved (the rule admits it: the toy's bytes fit) against
    ``"recompute"``: the loss bit for bit — the saved value is the array the
    forward made, in the calculation dtype —, the gradients to reconstruction
    ulps, and the backward of a region that holds a ``mamba`` layer runs ONE
    matmul of the in-projection's shape fewer: the replay's."""
    from homebrewnlp_tpu.model import remat
    got = {}
    for policy in ("recompute", "auto"):
        _, params, model, batch, variables = _build(
            dtype, block_config=SHORT, remat_policy=policy)
        assert remat.stash_plan(params)["recurrent"] == (
            (2, 2 * 2 * 64 * 100 * jnp.dtype(dtype).itemsize)
            if policy == "auto" else (0, 0))
        variables = {k: jnp.asarray(v) for k, v in variables.items()}
        fn = jax.value_and_grad(harness.loss_of(model))
        got[policy] = (_replayed_in_projections(
            jax.make_jaxpr(fn)(variables, batch).jaxpr),
            jax.jit(fn)(variables, batch))
    (replayed, (want_loss, want)), (saved, (loss, grads)) = \
        got["recompute"], got["auto"]
    # SHORT: mamba, mlp, attention, mlp, mamba
    assert replayed == [1, 0, 0, 0, 1] and saved == [0] * 5
    assert float(loss) == float(want_loss) and np.isfinite(float(loss))
    assert set(grads) == set(want)
    tight = dtype == "float32"
    for name in want:
        np.testing.assert_allclose(
            np.asarray(grads[name], np.float32),
            np.asarray(want[name], np.float32), err_msg=name,
            rtol=2e-4 if tight else 2e-2, atol=1e-6 if tight else 1e-4)


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "kernel"])
def step_reports_the_log_decay_watch_test(kernel, monkeypatch):
    """Two steps of the trainer: the loss is finite and falls, the second
    call publishes the first step's ``hbnlp_ssd_log_decay_min`` — the same
    number with the scan's Pallas pair (PR 48, interpreted) as with XLA's
    einsums: the first step's is the minimum of the same cumulative sum."""
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.train import Trainer
    if kernel:
        harness.steer_mamba_scan(monkeypatch)
    _, params, model, batch, _ = _build(
        "float32", block_config=SHORT, telemetry_enabled=True,
        sequence_length=32,
        learning_rate=0.01,
        learning_rate_config={"linear_warmup": {"final_step": 1}})
    batch = {k: v[:, :32] for k, v in batch.items()}
    trainer = Trainer(params, model)
    state = trainer.init_state(batch)
    losses, lows = [], []
    for _ in range(3):
        state, metrics = trainer.step(state, batch)
        jax.block_until_ready(metrics["loss"])
        losses.append(float(metrics["loss"]))
        lows.append(float(metrics["ssd_log_decay_min"]))
        assert lows[-1] < 0
    assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    snap = telemetry.registry().snapshot()
    assert snap["hbnlp_ssd_log_decay_min"]["series"][()] < 0
    _LOG_DECAY_MIN.append(lows[0])
    assert _LOG_DECAY_MIN[0] == lows[0]


_LOG_DECAY_MIN = []


