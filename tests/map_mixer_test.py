"""Pallas blocked learned-map mixer (parallel/map_mixer.py) parity guards.

The flagship mixer route (spatial.py `_maybe_map_mixer`) must match the
dense einsum path numerically — loss to 4 decimals, updated params to
tolerance — through both dispatch arms (fused XLA reference off-TPU and the
real kernel bodies in interpret mode), must skip causally-dead blocks
correctly at multi-block shapes, and must decline LOUDLY (naming why) at
unsupported shapes while keeping the dense result.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model
from homebrewnlp_tpu.train import Trainer

FLAGS = "biased_attention_map-absolute-input_as_value-shared"


def _cfg(knob, seq=128, **over):
    cfg = {
        "model_mode": "gpt", "use_video": False, "use_language": True,
        "sequence_length": seq, "features_per_head": 16, "heads": 2,
        "depth": 2, "train_batch_size": 2, "vocab_size": 64,
        "group_linear_factor": 2,
        "intermediate_feed_forward_multiplier_multiplier": 0.5,
        "memory_reduction_strategy": "none",
        "block_config": [{"layer": ["norm-shift-scale-features-group",
                                    f"attention-{FLAGS}"]}],
        "optimizer": "adam-learning_rate", "learning_rate": 0.003,
        "weight_decay": 0.0, "calculation_dtype": "float32",
        "storage_dtype": "float32", "slice_dtype": "float32",
        "use_map_mixer_kernel": knob, "model_path": "/tmp/map_mixer_test",
    }
    cfg.update(over)
    return ModelParameter(cfg)


def _step(knob, seq=128, mesh=None, **over):
    import jax
    import jax.numpy as jnp
    params = _cfg(knob, seq, **over)
    model = Model(params)
    if mesh is not None:
        from homebrewnlp_tpu.core import sharding as shardlib
        mesh = shardlib.build_mesh(params, jax.devices()[:4])
    trainer = Trainer(params, model, mesh=mesh)
    rng = np.random.default_rng(0)
    x = rng.integers(0, params.vocab_size,
                     (params.train_batch_size, seq, 1))
    batch = {"token_x": jnp.asarray(x),
             "token_y": jnp.asarray((x + 1) % params.vocab_size)}
    state = trainer.init_state(batch)
    state, metrics = trainer.step(state, batch, rng=jax.random.PRNGKey(3))
    return state, metrics


def _assert_step_parity(state_d, metrics_d, state_k, metrics_k, rtol=1e-4):
    # the ISSUE-level guard: loss to 4 decimals; params pin the backward
    assert abs(float(metrics_k["loss"]) - float(metrics_d["loss"])) < 1e-4, \
        (float(metrics_k["loss"]), float(metrics_d["loss"]))
    for name in state_d.variables:
        np.testing.assert_allclose(
            np.asarray(state_k.variables[name]),
            np.asarray(state_d.variables[name]), rtol=rtol, atol=1e-6,
            err_msg=name)


def map_mixer_route_matches_dense_test():
    state_d, metrics_d = _step(False)
    state_k, metrics_k = _step(True)
    _assert_step_parity(state_d, metrics_d, state_k, metrics_k)


def map_mixer_interpret_kernels_match_dense_test(monkeypatch):
    """The real pallas kernel bodies (interpret mode off-TPU), not the XLA
    reference arm: forward + custom_vjp backward through a full train
    step."""
    state_d, metrics_d = _step(False)
    monkeypatch.setenv("HBNLP_MAP_MIXER_INTERPRET", "1")
    state_k, metrics_k = _step(True)
    _assert_step_parity(state_d, metrics_d, state_k, metrics_k)


def map_mixer_sharded_matches_unsharded_test():
    # data x model mesh: the shard_map route (batch on 'data', heads on
    # 'model' — the bias map shards by head) must match the unmeshed step
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    state_m, metrics_m = _step(True, heads=4, mesh=True, tpu_size=4,
                               mesh_shape_override={"data": 2, "model": 2})
    state_u, metrics_u = _step(True, heads=4)
    _assert_step_parity(state_u, metrics_u, state_m, metrics_m, rtol=2e-4)


def map_mixer_kernel_blocked_causal_test():
    """Direct flat-core parity at a multi-block shape: interior blocks,
    diagonal-crossing blocks, and fully-dead skipped blocks all live in one
    [256, 256] map at 64-wide tiles; grads pin the dval/dbias kernels."""
    import jax
    import jax.numpy as jnp
    from homebrewnlp_tpu.parallel.map_mixer import _xla_reference, map_mixer
    rng = np.random.default_rng(1)
    h, s, f, b = 2, 256, 16, 2
    bias = jnp.asarray(rng.normal(size=(h, s, s)), jnp.float32)
    v4 = jnp.asarray(rng.normal(size=(b, s, h, f)), jnp.float32)
    vt = v4.transpose(0, 2, 1, 3).reshape(b * h, s, f)
    for causal in (True, False):
        def k_loss(bias_, vt_):
            return jnp.sum(map_mixer(bias_, vt_, causal, 64, 64, True) ** 2)

        def r_loss(bias_, v_):
            return jnp.sum(_xla_reference(bias_, v_, causal) ** 2)

        out_k = map_mixer(bias, vt, causal, 64, 64, True)
        out_r = _xla_reference(bias, v4, causal)
        np.testing.assert_allclose(
            np.asarray(out_k.reshape(b, h, s, f).transpose(0, 2, 1, 3)),
            np.asarray(out_r), rtol=1e-5, atol=1e-5,
            err_msg=f"causal={causal}")
        db_k, dv_k = jax.grad(k_loss, argnums=(0, 1))(bias, vt)
        db_r, dv_r = jax.grad(r_loss, argnums=(0, 1))(bias, v4)
        # atol 1e-3: the kernel's batch sweep reorders the f32
        # accumulation vs the reference einsum (values are O(10-100))
        np.testing.assert_allclose(np.asarray(db_k), np.asarray(db_r),
                                   rtol=1e-4, atol=1e-3,
                                   err_msg=f"dbias causal={causal}")
        np.testing.assert_allclose(
            np.asarray(dv_k.reshape(b, h, s, f).transpose(0, 2, 1, 3)),
            np.asarray(dv_r), rtol=1e-4, atol=1e-3,
            err_msg=f"dval causal={causal}")


def map_mixer_loud_fallback_test(capsys):
    """Unsupported shapes decline LOUDLY, naming why, and keep the dense
    result: seq 96 trips the 128-multiple tile gate."""
    from homebrewnlp_tpu.model import spatial
    spatial._MAP_MIXER_FALLBACK_SEEN.clear()
    _, metrics_k = _step(True, seq=96)
    out = capsys.readouterr().out
    assert "map-mixer kernel fallback" in out, out
    assert "128-multiple" in out, out
    _, metrics_d = _step(False, seq=96)
    assert abs(float(metrics_k["loss"]) - float(metrics_d["loss"])) < 1e-6


def map_mixer_knob_off_is_silent_test(capsys):
    from homebrewnlp_tpu.model import spatial
    spatial._MAP_MIXER_FALLBACK_SEEN.clear()
    _step(False)
    assert "map-mixer kernel fallback" not in capsys.readouterr().out


@pytest.mark.parametrize("s", [128, 512],
                         ids=["one_tile", "tiles_with_dead_blocks"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def map_mixer_dbias_sums_the_batch_in_the_kernel_test(s, causal):
    """The map's gradient out of the kernel that sweeps the batch inside
    (interpret mode, 128-wide tiles) against the reference's: one tile (the
    flagship's form: the only block crosses the diagonal), and a 4 x 4 grid
    of tiles with interior, diagonal and dead blocks.  An odd batch, so a
    sweep that stopped early or began late shows."""
    import jax
    import jax.numpy as jnp
    from homebrewnlp_tpu.parallel.map_mixer import _xla_reference, map_mixer
    rng = np.random.default_rng(5)
    b, h, f = 3, 2, 16
    bias = jnp.asarray(rng.normal(size=(h, s, s)), jnp.float32)
    v4 = jnp.asarray(rng.normal(size=(b, s, h, f)), jnp.float32)
    vt = v4.transpose(0, 2, 1, 3).reshape(b * h, s, f)
    db_k = jax.grad(lambda bias_: jnp.sum(
        map_mixer(bias_, vt, causal, 128, 128, True) ** 2))(bias)
    db_r = jax.grad(lambda bias_: jnp.sum(
        _xla_reference(bias_, v4, causal) ** 2))(bias)
    np.testing.assert_allclose(np.asarray(db_k), np.asarray(db_r),
                               rtol=1e-4, atol=1e-3)
    if causal:  # dead cells are zeros the kernel wrote, not leftovers
        assert not np.triu(np.asarray(db_k), 1).any()


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs it calls into (the
    counter's own table, utils/flops.py ``_descend``); kernel bodies are
    leaves there."""
    from homebrewnlp_tpu.utils.flops import _descend
    for eqn in jaxpr.eqns:
        yield eqn
        inner = _descend(eqn)
        if inner is not None:
            yield from _walk_eqns(inner[0])


def map_mixer_backward_holds_no_per_batch_map_test():
    """Structure of the mixer's backward: the dbias call writes ONE
    [heads, s, t] map; no value of shape [batch·heads, s, t] (or its
    unfolded form) exists, and no reduce_sum follows the call."""
    import jax
    import jax.numpy as jnp
    from homebrewnlp_tpu.parallel.map_mixer import map_mixer
    b, h, s, f = 3, 2, 256, 16
    bias = jnp.zeros((h, s, s), jnp.float32)
    vt = jnp.zeros((b * h, s, f), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(lambda bias_, v_: jnp.sum(
        map_mixer(bias_, v_, True, 128, 128, True) ** 2), argnums=(0, 1)))(
        bias, vt)
    eqns = list(_walk_eqns(jaxpr.jaxpr))
    calls = {str(e.params["name"]): e for e in eqns
             if e.primitive.name == "pallas_call"}
    assert sorted(calls) == ["map_mixer_bwd_dbias_causal",
                             "map_mixer_bwd_dval_causal",
                             "map_mixer_fwd_causal"]
    dbias = calls["map_mixer_bwd_dbias_causal"]
    assert [tuple(o.shape) for o in dbias.params["out_avals"]] \
        == [(h, s, s)]
    assert tuple(dbias.params["grid_mapping"].grid) == (h, 2, 2, b)
    shapes = {tuple(v.aval.shape) for e in eqns
              for v in list(e.invars) + list(e.outvars)
              if hasattr(v.aval, "shape")}
    assert (b * h, s, s) not in shapes and (b, h, s, s) not in shapes, shapes
    after = eqns[eqns.index(dbias) + 1:]
    assert not [e for e in after if e.primitive.name == "reduce_sum"], after


@pytest.mark.parametrize("batch,heads", [(32, 8), (128, 4)],
                         ids=["flagship_chip", "dp2tp2_chip"])
def map_mixer_backward_compiles_to_one_map_test(v5e, batch, heads):
    """The map mixer's backward at one chip's share of the two flagship
    cells (sequence 512, 512 features a head, bfloat16), compiled for a v5e:
    Mosaic accepts the batch-sweeping dbias kernel, it writes ONE float32
    ``[heads, 512, 512]`` map, and no per-(batch, head) map is left in the
    program."""
    import re
    from homebrewnlp_tpu.parallel import map_mixer as mm
    s = f = 512
    bias = jax.ShapeDtypeStruct((heads, s, s), jnp.bfloat16, sharding=v5e)
    act = jax.ShapeDtypeStruct((batch * heads, s, f), jnp.bfloat16,
                               sharding=v5e)
    block = mm.kernel_block(s, cap=512)
    hlo = jax.jit(lambda bias_, v, g: mm._bwd_impl(
        bias_, v, g, True, block, block, False)).lower(
        bias, act, act).compile().as_text()
    calls = dict(re.findall(r"%(map_mixer_\w+?)(?:\.\d+)? = (\w+\[[\d,]*\])",
                            hlo))
    assert calls == {
        "map_mixer_bwd_dbias_causal": f"f32[{heads},512,512]",
        "map_mixer_bwd_dval_causal": f"bf16[{batch * heads},512,512]"}, calls
    assert f"f32[{batch * heads},512,512]" not in hlo
    assert f"f32[{batch},{heads},512,512]" not in hlo
