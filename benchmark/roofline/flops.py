"""Count the matmul FLOPs of a jaxpr (the benchmark's own copy).

``2 * batch * m * n * k`` over every ``dot_general``, descending into
nested calls; scans multiply by their length.  The program has the same
walk in ``utils/flops.py``, where it also steers the program; this copy
only checks ``costs.forward_flops_per_token`` against the plain reference.
"""
from __future__ import annotations

import math

import jax


def _dot_flops(eqn) -> int:
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    (lc, rc), (lb, _rb) = eqn.params["dimension_numbers"]
    batch = math.prod(lhs.shape[i] for i in lb)
    k = math.prod(lhs.shape[i] for i in lc)
    m = math.prod(d for i, d in enumerate(lhs.shape)
                  if i not in set(lc) | set(lb))
    n = math.prod(d for i, d in enumerate(rhs.shape)
                  if i not in set(rc) | set(_rb))
    return 2 * batch * m * n * k


def _inner(eqn):
    """``[(jaxpr, multiplier)]`` of the calls an equation holds."""
    out = []
    for key, value in eqn.params.items():
        for item in (value if isinstance(value, (list, tuple)) else [value]):
            jaxpr = getattr(item, "jaxpr", item)
            if hasattr(jaxpr, "eqns"):
                mult = int(eqn.params["length"]) \
                    if eqn.primitive.name == "scan" else 1
                out.append((jaxpr, mult))
    return out


def count(jaxpr) -> int:
    total = 0
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "dot_general":
            total += _dot_flops(eqn)
        for inner, mult in _inner(eqn):
            total += mult * count(inner)
    return total


def forward_flops(fn, *args) -> int:
    """Matmul FLOPs of ``fn(*args)``, traced abstractly."""
    return count(jax.make_jaxpr(fn)(*args))
