"""Optimizer-chain semantics tests against hand-rolled numpy references.

The chain members' exact formulas (SM3 min-bucket, AGC, Nesterov momentum,
debiased Adam, grafting) are the reference's loss-parity-critical parts
(SURVEY.md §7 hard part 1); each is locked down numerically here.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from backend import make_params
from homebrewnlp_tpu.core.dims import Dim
from homebrewnlp_tpu.optim import Optimizer, is_large_tensor, parse_chain
from homebrewnlp_tpu.optim.learning_rate import get_learning_rate


def _run_chain(optimizer, shapes, steps=3, seed=0, lr=0.01, **cfg):
    params = make_params(optimizer=optimizer, learning_rate=lr, weight_decay=0.0,
                         **cfg)
    rng = np.random.default_rng(seed)
    variables = {name: jnp.asarray(rng.standard_normal(shape).astype(np.float32))
                 for name, shape in shapes.items()}
    dims = {name: tuple(Dim(f"d{i}", s) for i, s in enumerate(shape))
            for name, shape in shapes.items()}
    opt = Optimizer(params, dims)
    state = opt.init(variables)
    grads_hist = []
    for step in range(steps):
        grads = {name: jnp.asarray(rng.standard_normal(v.shape).astype(np.float32))
                 for name, v in variables.items()}
        grads_hist.append({k: np.asarray(v) for k, v in grads.items()})
        variables, state, _ = opt.update(variables, grads, state,
                                         jnp.asarray(step, jnp.int32))
    return variables, grads_hist, params


def sgd_learning_rate_test():
    """optimizer='learning_rate' is plain SGD: v -= lr * g."""
    shapes = {"w": (4, 5)}
    out, grads, params = _run_chain("learning_rate", shapes, steps=2, lr=0.1)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((4, 5)).astype(np.float32)
    for g in grads:
        w = w - 0.1 * g["w"]
    np.testing.assert_allclose(np.asarray(out["w"]), w, rtol=1e-5)


def momentum_nesterov_test():
    """momentum:0.9:1:1 (Nesterov) semantics (optimizers.py:118-128)."""
    shapes = {"w": (3, 3)}
    out, grads, _ = _run_chain("momentum:0.9:1:1-learning_rate", shapes,
                               steps=3, lr=0.1)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((3, 3)).astype(np.float32)
    state = np.zeros_like(w)
    for g in grads:
        state = 0.9 * state + g["w"]
        upd = g["w"] + 0.9 * state
        w = w - 0.1 * upd
    np.testing.assert_allclose(np.asarray(out["w"]), w, rtol=1e-5)


def sm3_test():
    """SM3 per-dim min-bucket accumulators (optimizers.py:60-76)."""
    shapes = {"w": (4, 6)}
    out, grads, _ = _run_chain("sm3-learning_rate", shapes, steps=3, lr=0.01)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((4, 6)).astype(np.float32)
    r = np.zeros(4, np.float32)
    c = np.zeros(6, np.float32)
    for g in grads:
        acc = np.minimum(r[:, None], c[None, :]) + g["w"] ** 2
        r = acc.max(1)
        c = acc.max(0)
        upd = g["w"] / np.maximum(np.sqrt(acc), 1e-5)
        w = w - 0.01 * upd
    np.testing.assert_allclose(np.asarray(out["w"]), w, rtol=1e-5)


def adam_test():
    shapes = {"w": (5,)}
    out, grads, _ = _run_chain("adam-learning_rate", shapes, steps=3, lr=0.01,
                               opt_beta1=0.9, opt_beta2=0.999)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((5,)).astype(np.float32)
    m = np.zeros_like(w)
    v = np.zeros_like(w)
    for t, g in enumerate(grads):
        v = 0.999 * v + 0.001 * g["w"] ** 2
        m = 0.9 * m + 0.1 * g["w"]
        # reference debias: 1/(1 - beta^(step+1))
        vh = v / (1 - 0.999 ** (t + 1))
        upd = m / np.maximum(np.sqrt(vh), 1e-5) / (1 - 0.9 ** (t + 1))
        w = w - 0.01 * upd
    np.testing.assert_allclose(np.asarray(out["w"]), w, rtol=2e-5)


def adaptive_clip_test():
    """AGC: g * min(||w|| * clip / ||g||, 1) (optimizers.py:79-84)."""
    shapes = {"w": (8, 8)}
    out, grads, _ = _run_chain("adaptive_clip:0.01-learning_rate", shapes,
                               steps=1, lr=1.0)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((8, 8)).astype(np.float32)
    g = grads[0]["w"]
    gn = np.minimum(1 / np.sqrt((g ** 2).sum()), 1e6)
    wn = np.maximum(np.sqrt((w ** 2).sum()), 1e-3)
    w_exp = w - g * min(wn * gn * 0.01, 1.0)
    np.testing.assert_allclose(np.asarray(out["w"]), w_exp, rtol=1e-5)


def graft_test():
    """graft:adam = direction of g, magnitude of adam's update."""
    shapes = {"w": (6, 6)}
    out, grads, _ = _run_chain("graft:adam-learning_rate", shapes, steps=1, lr=1.0)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((6, 6)).astype(np.float32)
    g = grads[0]["w"]
    v = 0.001 * g ** 2 / (1 - 0.999)
    m = 0.1 * g / (1 - 0.9)
    adam_upd = m / np.maximum(np.sqrt(v), 1e-5)
    upd = g / np.sqrt((g ** 2).sum()) * np.sqrt((adam_upd ** 2).sum())
    np.testing.assert_allclose(np.asarray(out["w"]), w - upd, rtol=1e-4)


def value_and_global_clip_test():
    shapes = {"w": (4,)}
    out, grads, _ = _run_chain("value_clip:0.001-learning_rate", shapes, steps=1, lr=1.0)
    rng = np.random.default_rng(0)
    w = rng.standard_normal((4,)).astype(np.float32)
    np.testing.assert_allclose(np.asarray(out["w"]),
                               w - np.clip(grads[0]["w"], -0.001, 0.001), rtol=1e-5)
    out, grads, _ = _run_chain("global_l2norm_clip:1.0-learning_rate",
                               {"a": (4,), "b": (3,)}, steps=1, lr=1.0)


def lr_schedule_test():
    """linear_warmup / exponential_decay / bounds DSL
    (reference learning_rate.py:27-63)."""
    params = make_params(learning_rate=0.01,
                         learning_rate_config={
                             "linear_warmup": {"final_step": 100},
                             "exponential_decay": {"start_step": 200, "factor": 0.99},
                             "lower_bound": {"factor": 1e-4}})
    lr = lambda s: float(get_learning_rate(params, jnp.asarray(s)))
    assert abs(lr(50) - 0.005) < 1e-7
    assert abs(lr(100) - 0.01) < 1e-7
    assert abs(lr(150) - 0.01) < 1e-7
    assert abs(lr(210) - 0.01 * 0.99 ** 10) < 1e-7
    assert lr(10 ** 6) == pytest.approx(1e-4)


def weight_decay_heuristics_test():
    """Name/shape heuristics for weight-decay eligibility (reference :49-61)."""
    params = make_params()
    h, k = params.head_dim, params.key_dim
    inter = params.intermediate[0]
    cases = [
        ("gpt0/body0/block0_0_0/bottleneck_group_linear_0/orthogonal_var0/var0",
         (h, k, inter), True),
        ("gpt0/body0/block0_0_0/norm_0/normal_var0/var0", (h, k), False),
        ("gpt0/body0/block0_1_0/attention_0/embed0/normal_var0/var0",
         (h, Dim("sequence", 16), Dim("_sequence", 16)), False),
        ("gpt0/input0/orthogonal_var0/var0",
         (Dim("language_token_patch", 1), inter, h, k), False),
        ("gpt0/output0/embed0/orthogonal_var0/var0",
         (h, k, Dim("language_token_patch", 1), Dim("vocab", 32)), False),
        ("gpt0/body0/block0_0_0/rezero_0/var0", (), False),
    ]
    for name, dims, expected in cases:
        size = int(np.prod([d.size for d in dims])) if dims else 1
        assert is_large_tensor(params, name, dims, size) == expected, name


def chain_parse_test():
    chain = parse_chain("adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate")
    assert [c[0] for c in chain] == ["adaptive_clip", "sm3", "momentum", "learning_rate"]
    assert chain[2][1] == ("0.9", "1", "1")
    with pytest.raises(ValueError):
        parse_chain("not_an_optimizer")
