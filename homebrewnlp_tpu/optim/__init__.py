"""Optimizer driver: the '-'/'-:' chain interpreter + update rule.

Reference: /root/reference/src/optimizer/__init__.py.  The reference
re-implements reverse-mode autodiff over the mtf graph (:143-174); here
gradients come from ``jax.grad`` and this module only performs the per-variable
update chain:

  for each var:  g -> chain members -> rezero LR multiplier -> selective
  weight decay (name/shape heuristics, :49-61) -> var -= g

State lives in a per-variable slot dict (optimizer_slice_dtype).  All of it is
a pure (params, grads, state, step) -> (params, state) function, jit/pjit
friendly, with the variable loop unrolled at trace time (XLA fuses the small
per-var element-wise chains).
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelParameter
from ..core.dims import Dim
from .learning_rate import get_learning_rate
from .optimizers import OPTIMIZERS, VarCtx, jax_rsqrt

Params = typing.Dict[str, jax.Array]
OptState = typing.Dict[str, typing.Dict[str, jax.Array]]


def _feature_dims_used(params: ModelParameter, dims: typing.Tuple[Dim, ...]) -> bool:
    names = [d.name.lstrip("_") for d in dims]
    return sum(f.name in names for f in params.feature_dims) >= 2


def is_large_tensor(params: ModelParameter, name: str,
                    dims: typing.Tuple[Dim, ...], size: int) -> bool:
    """Weight-decay eligibility heuristics (reference :49-61)."""
    features_used = _feature_dims_used(params, dims)
    large = features_used and len(dims) > len(params.feature_dims)
    large |= (not features_used) and len(dims) >= 2
    large &= size > 1
    large &= "norm" not in name
    large &= "rezero" not in name
    large &= "embed" not in name
    large &= "input" not in name or "lang_in" in name or "vid_in" in name
    large &= "output" not in name or "lang_out" in name or "vid_out" in name
    return bool(large)


def selection_bias_rule(bias, counts, rate: float):
    """DeepSeek-V3's auxiliary-loss-free balancing (arXiv:2412.19437 section
    2.1.2): ``b_e <- b_e + rate x sign(mean(c) - c_e)`` with ``counts`` the
    step's (token, choice) pairs of every expert of the layer (the last
    axis), as the backward of model/moe.py ``_balance_tap`` hands them over:
    an expert above the mean load is chosen less next step, one below it
    more."""
    counts = counts.astype(jnp.float32)
    return (bias.astype(jnp.float32) + rate * jnp.sign(
        jnp.mean(counts, axis=-1, keepdims=True) - counts)).astype(bias.dtype)


#: the scope layer ``moe`` makes its selection bias under (model/moe.py
#: imports it from here: ``optim/`` lies below ``model/``)
SELECTION_BIAS = "selection_bias"

#: the mark in its name -> the rule ``(params, value, handed) -> value`` of a
#: leaf that is no weight of the loss: the step moves it from what the
#: backward hands it in the place of a gradient — outside the chain, the
#: clip's global norm, the weight decay and the slots
OWN_RULES = {
    SELECTION_BIAS: lambda params, bias, counts: selection_bias_rule(
        bias, counts, params.moe_bias_rate),
}


def own_rule(name: str):
    """The rule of its own that moves the leaf ``name``, or None."""
    return next((rule for mark, rule in OWN_RULES.items() if mark in name),
                None)


def parse_chain(optimizer: str) -> typing.List[typing.Tuple[str, typing.Tuple[str, ...]]]:
    chain = []
    for member in optimizer.split("-"):
        name, *args = member.split(":")
        if name not in OPTIMIZERS:
            raise ValueError(f"unknown optimizer chain member {name!r}")
        chain.append((name, tuple(args)))
    return chain


def _zeros_for(variable, shape, dtype):
    """Zero slot laid out like its variable: same-shape slots inherit the
    variable's sharding, reduced-shape slots (SM3 per-dim buckets, scalars)
    replicate over the same mesh.  A bare ``jnp.zeros`` would commit to the
    process-local default device — mixed with globally-sharded variables in
    one jit, a multi-controller run rejects that ('incompatible devices')."""
    if isinstance(variable, jax.Array) and isinstance(
            variable.sharding, jax.sharding.NamedSharding):
        mesh = variable.sharding.mesh
        sharding = variable.sharding if tuple(shape) == tuple(variable.shape) \
            else jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        host = np.zeros(shape, dtype)
        return jax.make_array_from_callback(tuple(shape), sharding,
                                            lambda idx: host[idx])
    return jnp.zeros(shape, dtype)


class Optimizer:
    def __init__(self, params: ModelParameter,
                 param_dims: typing.Dict[str, tuple]):
        self.params = params
        self.param_dims = param_dims
        self.chain = parse_chain(params.optimizer)
        self._needs_global_norm = any(n == "global_l2norm_clip" for n, _ in self.chain)

    def init(self, variables: Params) -> OptState:
        """Zero-initialised slots, discovered by abstractly tracing the chain."""
        state: OptState = {}
        opt_dtype = self.params.optimizer_slice_dtype
        calc = self.params.optimizer_calculation_dtype
        for name, value in variables.items():
            if own_rule(name):
                state[name] = {}
                continue

            def _shapes(shape=value.shape):
                ctx = VarCtx(name=name,
                             grad=jnp.zeros(shape, calc),
                             value=jnp.zeros(shape, calc),
                             slots={}, new_slots={},
                             learning_rate=jnp.float32(0),
                             beta1=jnp.float32(self.params.opt_beta1),
                             beta2=jnp.float32(self.params.opt_beta2),
                             step_count=jnp.float32(1),
                             global_norm_reciprocal=jnp.float32(1)
                             if self._needs_global_norm else None,
                             slot_dtype=opt_dtype)
                for opt_name, args in self.chain:
                    ctx.grad = OPTIMIZERS[opt_name](ctx, *args)
                return ctx.new_slots
            slots = jax.eval_shape(_shapes)
            state[name] = {k: _zeros_for(value, v.shape, opt_dtype)
                           for k, v in slots.items()}
        return state

    def update(self, variables: Params, grads: Params, state: OptState,
               global_step: jax.Array) -> typing.Tuple[Params, OptState, jax.Array]:
        """One optimizer step; returns (new_vars, new_state, learning_rate)."""
        p = self.params
        calc = p.optimizer_calculation_dtype
        lr = get_learning_rate(p, global_step).astype(calc)
        # reference step bookkeeping (:89-96): with grad_accumulation==1 the
        # debias exponent is global_step + 1
        step_count = jnp.asarray(global_step, calc) + 1
        beta1 = jnp.asarray(p.opt_beta1, calc)
        beta2 = jnp.asarray(p.opt_beta2, calc)

        global_norm_recip = None
        if self._needs_global_norm:
            clip = next(float(a[0]) for n, a in self.chain if n == "global_l2norm_clip")
            total = sum(jnp.sum(jnp.square(g.astype(calc)))
                        for name, g in grads.items() if not own_rule(name))
            global_norm_recip = jax_rsqrt(jnp.maximum(total, clip ** -2))

        new_vars: Params = {}
        new_state: OptState = {}
        for name, value in variables.items():
            rule = own_rule(name)
            if rule is not None:
                new_vars[name] = rule(p, value, grads[name])
                new_state[name] = {}
                continue
            grad = grads[name].astype(calc)
            ctx = VarCtx(name=name, grad=grad, value=value.astype(calc),
                         slots=state.get(name, {}), new_slots={},
                         learning_rate=lr, beta1=beta1, beta2=beta2,
                         step_count=step_count,
                         global_norm_reciprocal=global_norm_recip,
                         slot_dtype=p.optimizer_slice_dtype)
            for opt_name, args in self.chain:
                ctx.grad = OPTIMIZERS[opt_name](ctx, *args)

            if "rezero" in name:
                ctx.grad = ctx.grad * p.rezero_lr_multiplier

            dims = self.param_dims.get(name, ())
            if p.weight_decay > 0 and is_large_tensor(p, name, dims, value.size):
                ctx.grad = ctx.grad + ctx.value * lr * p.weight_decay

            new_vars[name] = (value.astype(calc) - ctx.grad).astype(value.dtype)
            new_state[name] = ctx.new_slots
        return new_vars, new_state, lr
