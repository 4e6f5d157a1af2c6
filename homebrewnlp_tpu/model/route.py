"""Pre-attention routing (layer ``route_early``; SmallThinker, PowerInfer,
arXiv:2507.20984 section 2).

The router of a sparse layer, run a block EARLY: on the input of the block
before it — the attention block's normed input ``u`` — not on the sparse
block's own:

    block A:  u = rms(h) w_1;  r = u W_r   [tokens, experts], float32
              h' = h + attention(u)
    block B:  x = rms(h') w_2; p = softmax(r); h'' = h' + experts(x, top-k(p))

In the block's chain the layer is a pass-through: ``['norm-rms-scale',
'route_early', 'attention-...']`` hands the attention what the norm made.  It
owns the router's one matrix ``W_r [features, experts]`` (normal(0.02); the
parameter lives in the block that reads it, so that block's
``jax.checkpoint`` region holds it) and leaves the logits in
``Context.side[ROUTER_LOGITS]``: a CARRIED SIDE VALUE (model/blocks.py), an
explicit output of block A's region and input of block B's, with its
cotangent in the backward.  Layer ``moe`` with flag ``routed_early``
(model/moe.py) takes them from there — it makes no router matrix and runs no
router matmul, forward, replay or backward — and routes, weighs and balances
as the one-matrix router does; the balance term's gradient
(``_router_aux_inject``) leaves block B through the carried value's cotangent
and reaches ``W_r`` and the stream in block A.  What the model's deployment
buys with it (the router's answer a whole attention call before the experts
are read, so that absent experts are fetched under it) is the serving path's;
in training it is one more value that crosses a block boundary.

Only the strategies that carry side values run it (``checkpoint`` / ``none``,
unrolled); the others refuse by name, as they refuse ``router_mlp``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..config import BlockArgs
from ..core import scope
from ..core.tensor import NamedTensor, transpose_to
from .backend import normal_var
from .utils import anonymize_dim

#: the key of the early router's logits in ``Context.side``
ROUTER_LOGITS = "router_logits"

#: the modes without carried side values, as the refusals name them
NO_SIDE_VALUES = ("scan_layers, revnet, momentum, a pipe mesh, decode, "
                  "prefill, the stats probe and the leading / trailing "
                  "blocks have none yet")


def matrix_logits(xf, w_router: NamedTensor):
    """The one-matrix router, here and in layer ``moe``: the rows ``xf [t,
    f]`` times ``w_router`` read as ``[f, experts]`` — the calculation
    dtype's operands, float32 accumulation off the CPU — in float32."""
    return jnp.dot(
        xf, w_router.data.reshape(xf.shape[-1], -1),
        preferred_element_type=None if jax.default_backend() == "cpu"
        else jnp.float32).astype(jnp.float32)


def route_early(args: BlockArgs) -> NamedTensor:
    """Layer ``route_early``: the ``experts`` router logits of the NEXT
    ``moe-...-routed_early`` layer from this layer's input, float32
    ``[tokens, experts]``, left in ``Context.side``; returns its input.  One
    parameter: the router's matrix ``[features, experts]``, normal(0.02).  No
    flags."""
    params = args.params
    ctx = scope.current()
    if args.name_extras:
        raise ValueError(f"layer route_early takes no flags, got "
                         f"{list(args.name_extras)}")
    if ctx.side is None:
        raise NotImplementedError(
            "layer route_early hands its logits to a later block's sparse "
            "layer (moe-...-routed_early), a carried side value that only the "
            f"unrolled checkpoint / none strategies hold: {NO_SIDE_VALUES}")
    feats = list(params.feature_dims)
    w_router = normal_var(args, [anonymize_dim(d) for d in feats]
                          + [params.expert_dim])
    x = args.tensor
    token_dims = [d for d in x.dims if d not in feats]
    f_sz = math.prod(d.size for d in feats)
    xf = transpose_to(x, token_dims + feats).data.reshape(-1, f_sz)
    ctx.side[ROUTER_LOGITS] = matrix_logits(xf, w_router)
    return x
