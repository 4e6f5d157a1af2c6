"""parallel/delta_solve.py: the Pallas kernel pair for the gated delta rule's
triangular solve (interpret mode on the CPU) against the float64 inverse,
XLA's blocked form (``model/gated_delta.py _blocked_inverse``) and autodiff's
gradient of it, the predicate that chooses between them, and the rule with
and without the kernels."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from homebrewnlp_tpu.model import gated_delta as delta_mod
from homebrewnlp_tpu.parallel import delta_solve as ds


def _near_coincident(batch, size, seed):
    """``blocked_solve_is_the_inverse_test``'s systems: entries 1.2 - 2.0
    below the diagonal — keys that nearly coincide, ``beta`` near 2."""
    rng = np.random.default_rng(seed)
    return np.tril(rng.uniform(1.2, 2.0, size=batch + (size, size)), -1)


def _off(got, want):
    """A system's largest error over its own largest entry, per system."""
    return np.abs(got - want).max((-1, -2)) / np.abs(want).max((-1, -2))


def _error(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.abs(got - want).max() / np.abs(want).max())


# batches that are no whole tile (zero systems are appended); two tiles and
# a part of a third at the smallest size
@pytest.mark.optimised
@pytest.mark.parametrize("batch,size", [
    ((19,), 16), ((2, 9), 32), ((17,), 64), ((16,), 128), ((2, 135), 16)])
def kernel_is_the_inverse_test(batch, size):
    """Against ``np.linalg.inv`` in float64: every system within the XLA
    form's bound of its own largest entry, AND over the systems no further
    off than twice the XLA form on the same input — which a product that
    drops a bfloat16 term is, thirty times over.  The second bound is on the
    MEAN of the systems' errors: these systems are ill-conditioned (entries
    of the inverse reach 1e5 at 128 rows), and one system's error under
    either algorithm is a draw that spreads 2.5 x either way (float32
    emulations of both in numpy, 16 systems x 4 seeds a size; the means
    stay within 0.68 - 1.40 of each other).  Nothing above the diagonal."""
    strict = _near_coincident(batch, size, size)
    want = np.linalg.inv(np.eye(size) + strict)
    got = np.asarray(ds.inverse_unit_lower(
        jnp.asarray(strict, jnp.float32), True))
    xla = np.asarray(delta_mod._blocked_inverse(
        jnp.asarray(strict, jnp.float32)))
    assert got.shape == want.shape and got.dtype == np.float32
    assert _off(got, want).max() <= 2e-5
    assert _off(got, want).mean() <= 2 * _off(xla, want).mean()
    assert not np.triu(got, 1).any()
    np.testing.assert_array_equal(np.diagonal(got, axis1=-2, axis2=-1), 1.0)


@pytest.mark.parametrize("batch,size", [
    ((3,), 16), ((2, 3), 32), ((3,), 64), ((2,), 128), ((40,), 16)])
def kernel_backward_is_autodiffs_test(batch, size):
    """``-strict_tril(X^T dX X^T)`` from the saved inverse, against
    ``jax.grad`` through the XLA form; milder systems, so that the gradient
    is no larger than float32 resolves to 1e-5 of it."""
    rng = np.random.default_rng(size)
    strict = jnp.asarray(np.tril(rng.uniform(-0.3, 0.3, batch + (size, size)),
                                 -1), jnp.float32)
    ct = jnp.asarray(rng.normal(size=strict.shape), jnp.float32)
    inv, vjp = jax.vjp(delta_mod._blocked_inverse, strict)
    want, = vjp(ct)
    got = ds.inverse_unit_lower_bwd(inv, ct, True)
    np.testing.assert_allclose(got, want, rtol=1e-5,
                               atol=1e-5 * float(jnp.abs(want).max()))
    assert not np.triu(np.asarray(got)).any()


@pytest.mark.parametrize("chunk,matrices,backend,applies", [
    (64, 2560, "tpu", True), (16, 128, "tpu", True), (128, 256, "tpu", True),
    (64, 2560, "cpu", False), (64, 2560, "gpu", False),
    (64, 2500, "tpu", False),         # no whole tiles of 128 systems
    (64, 0, "tpu", False),
    (8, 128, "tpu", False), (256, 128, "tpu", False),
    (48, 128, "tpu", False), (5, 128, "tpu", False)])
def predicate_test(chunk, matrices, backend, applies):
    assert ds.solve_kernel_applies(chunk, matrices, backend) is applies


def predicate_reads_the_backend_test():
    assert jax.default_backend() == "cpu"
    assert not ds.solve_kernel_applies(64, 2560)


@pytest.fixture
def through_the_kernels(monkeypatch):
    """Layer ``gated_delta`` as a TPU process dispatches it, the kernels
    interpreted."""
    monkeypatch.setattr(delta_mod, "solve_kernel_applies", functools.partial(
        ds.solve_kernel_applies, backend="tpu"))
    monkeypatch.setattr(delta_mod, "inverse_unit_lower", functools.partial(
        ds.inverse_unit_lower, interpret=True))
    monkeypatch.setattr(delta_mod, "inverse_unit_lower_bwd", functools.partial(
        ds.inverse_unit_lower_bwd, interpret=True))


def _rule_inputs(bsz, s, h, dk, dv, seed=0):
    rng = np.random.default_rng(seed)

    def unit(t):
        return t / np.linalg.norm(t, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(bsz, s, h, dk))) * dk ** -0.5
    k = unit(rng.normal(size=(bsz, s, h, dk)))
    v = rng.normal(size=(bsz, s, h, dv))
    beta = rng.uniform(0.2, 1.9, size=(bsz, s, h))
    g = -rng.uniform(0.01, 0.5, size=(bsz, s, h))
    weights = rng.normal(size=(bsz, s, h, dv))
    return tuple(jnp.asarray(t, jnp.float32)
                 for t in (q, k, v, beta, g)), jnp.asarray(weights,
                                                           jnp.float32)


# 2 x 8 chunks x 8 heads = 128 systems of 16 x 16 a call, one tile; the
# grouped rule under a budget of four heads a group = 2 x 16 x 4
@pytest.mark.parametrize("rule,s,budget", [
    ("delta_rule", 128, None), ("grouped_rule", 256, 4 * 2 * 256 * 16 * 4)])
def rule_through_the_kernels_test(rule, s, budget, monkeypatch,
                                  request):
    """The rule's output, its gauge and all five gradients with the solve
    and its backward as the Pallas pair against XLA's blocked form: two
    float32 roundings of one system, so a tenth of what
    ``tests/olmo_hybrid_test.py`` allows the chunked rule against the
    recurrence (2e-5 of the output's largest entry, 1e-4 of a
    gradient's)."""
    inputs, weights = _rule_inputs(2, s, 8, 8, 16)
    if budget is not None:
        monkeypatch.setattr(delta_mod, "GROUP_BYTES", budget)
        assert delta_mod._group_heads(2, s, 8, 16) == 4

    def run():
        def loss(*args):
            o, biggest = getattr(delta_mod, rule)(*args, 16)
            return jnp.sum(o * weights), (o, biggest)
        return jax.jit(jax.value_and_grad(loss, argnums=range(5),
                                          has_aux=True))(*inputs)

    (_, (want, want_max)), wants = run()
    request.getfixturevalue("through_the_kernels")
    text = str(jax.make_jaxpr(lambda *a: getattr(delta_mod, rule)(*a, 16))(
        *inputs))
    assert "delta_solve_fwd" in text
    (_, (got, got_max)), grads = run()
    assert _error(got, want) <= 2e-6
    np.testing.assert_allclose(got_max, want_max, rtol=1e-6)
    for name, a, r in zip("q k v beta g".split(), grads, wants):
        assert _error(a, r) <= 1e-5, name


def declining_rule_traces_the_blocked_form_test():
    """On the CPU (and wherever the predicate declines) the rule traces no
    Pallas call: XLA's blocked form, unchanged."""
    inputs, _ = _rule_inputs(2, 128, 8, 8, 16)
    text = str(jax.make_jaxpr(lambda *a: delta_mod.delta_rule(*a, 16))(
        *inputs))
    assert "pallas_call" not in text and "delta_solve" not in text
