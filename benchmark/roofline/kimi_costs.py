"""Required operations and bytes of the Kimi-Linear configuration as one rank
of an expert-parallel group holds it, computed from shapes.

From the layer equations in ``benchmark/reference/kimi_linear_48b_a3b.py``.
A KDA layer with ``H`` heads of ``d_k`` / ``d_v`` and low rank ``r = d_v`` on a
stream ``d`` wide: the projections ``d x (2 H d_k + H d_v)``, the decay pair
``d x r`` + ``r x H d_k``, the gate pair ``d x r`` + ``r x H d_v``, ``beta``
``d x H``, the rule, the out-projection ``H d_v x d``.  A latent attention
layer with ``Q`` heads of key ``k + r_s`` and value ``k`` from a latent ``c``:
the query ``d x Q (k + r_s)``, the projection down ``d x (c + r_s)``, the
projection up ``c x Q 2 k``, the output ``Q k x d``, scores at the key's
width and weighted values at the value's over the lower triangle.  The dense
MLP: three ``d x i`` matmuls.  A sparse layer: the router over ALL routed
experts ``d x E``, the shared expert's three matmuls ``d x W``, and the
routed experts HELD HERE, three matmuls ``d x I`` each: of a token's
``top_k`` choices the share ``held / experts`` lands on this rank when the
router is balanced.  Then the head over this rank's rows of the vocabulary.
"Required" is what the mathematics needs in the form the configuration
states — the chunked rule at ``KDA_CHUNK`` positions a chunk, lower triangles
inside a chunk, the triangular system by substitution, the lower triangle of
the attention scores — and nothing masked or recomputed.  A pair of the
rule's two decayed products costs what a matmul's pair costs (``2 d_k``: the
decay is inside the sum over the channels, a factor and not a term).  Norms,
the conv's four multiplies, the L2 normalisation, softplus, sigmoids and the
decays are not matmuls.

The layers are read from the configuration's layer strings, as the reference
reads them.
"""
from __future__ import annotations

import typing

from . import costs


def _number(flags, name: str, default=None) -> int:
    return next((int(f[len(name):]) for f in flags
                 if f.startswith(name) and f[len(name):].isdigit()), default)


def layers(config: dict) -> typing.List[dict]:
    """Every sublayer in execution order: ``{"kind": "kda"}``, ``{"kind":
    "latent", "heads", "latent", "shared"}``, ``{"kind": "dense"}`` or
    ``{"kind": "sparse"}``."""
    out = []
    for block in list(config["block_config"]) * config["depth"]:
        for layer in block["layer"]:
            name, *flags = layer.split("-")
            if name == "attention" and _number(flags, "kv_latent"):
                out.append({"kind": "latent",
                            "heads": _number(flags, "q_heads",
                                             config["heads"]),
                            "latent": _number(flags, "kv_latent"),
                            "shared": _number(flags, "shared_key", 0)})
            elif name in ("kda", "mlp", "moe"):
                out.append({"kind": {"kda": "kda", "mlp": "dense",
                                     "moe": "sparse"}[name]})
    return out


def count(config: dict, kind: str) -> int:
    return sum(layer["kind"] == kind for layer in layers(config))


def _stream(config: dict) -> int:
    return config["heads"] * config["features_per_head"]


#: positions a chunk of the rule's WY form (``model/kda.py CHUNK``: the model
#: does not depend on it, the count of its operations does)
KDA_CHUNK = 64


def _chunk(config: dict) -> int:
    return min(KDA_CHUNK, config["sequence_length"])


def rule_flops_per_token(config: dict, executed: bool = False) -> float:
    """The chunked rule's operations for one token of one layer, all heads.
    Inside the chunk, over the keys a position meets — ``(chunk - 1) / 2``
    before it for the decayed ``K K^T``, ``(chunk + 1) / 2`` up to it for
    the decayed ``Q K^T``, ``T K``, ``T V`` and the weighted ``V'`` —; the
    unit lower triangular inverse, ``chunk^3 / 3`` a chunk by substitution;
    and three products with the ``d_k x d_v`` state: ``W S``, ``Q S`` and the
    state's own update.  ``executed``: what the plain reference runs instead,
    the recurrence's two products with the state a position (``S^T k`` and
    ``S^T q``; its decay and its rank-one update are no matmuls)."""
    h, dk, dv = (config["kda_heads"], config["kda_key_features"],
                 config["kda_value_features"])
    if executed:
        return h * 2 * 2 * dk * dv
    c = _chunk(config)
    before, upto = (c - 1) / 2, (c + 1) / 2
    return h * (2 * dk * before + 2 * dk * upto       # K K^T, Q K^T, decayed
                + c * c / 3
                + 2 * dk * upto + 2 * 2 * dv * upto   # T K; T V, (QK) V'
                + 3 * 2 * dk * dv)


def kda_flops_per_token(config: dict, executed: bool = False) -> float:
    d, r = _stream(config), config["kda_value_features"]
    h, dk, dv = (config["kda_heads"], config["kda_key_features"],
                 config["kda_value_features"])
    return 2 * d * (2 * h * dk + h * dv) + 2 * (d * r + r * h * dk) \
        + 2 * (d * r + r * h * dv) + 2 * d * h \
        + rule_flops_per_token(config, executed) + 2 * h * dv * d


def latent_flops_per_token(config: dict, layer: dict,
                           executed: bool = False) -> float:
    d, k, s = _stream(config), config["features_per_head"], \
        config["sequence_length"]
    q, c, r = layer["heads"], layer["latent"], layer["shared"]
    keys = float(s) if executed else costs.causal_pairs(s) / s
    return 2 * d * q * (k + r) + 2 * d * (c + r) + 2 * c * q * 2 * k \
        + 2 * q * k * d + q * (2 * (k + r) + 2 * k) * keys


def dense_flops_per_token(config: dict) -> float:
    d = _stream(config)
    return 3 * 2 * d * int(
        d * config["intermediate_feed_forward_multiplier"])


def sparse_parts_per_token(config: dict, executed: bool = False
                           ) -> typing.Dict[str, float]:
    """The sparse layer's matmul FLOPs a token, by part.  ``executed``:
    every held expert on every token, as the plain reference runs them."""
    d = _stream(config)
    held = config.get("experts_held") or config["experts"]
    routed = held if executed \
        else config["moe_top_k"] * held / config["experts"]
    return {"router": 2 * d * config["experts"],
            "shared": 3 * 2 * d * config["shared_expert_width"],
            "held": routed * 3 * 2 * d * config["expert_width"]}


def forward_flops_per_token(config: dict, executed: bool = False) -> float:
    """Matmul FLOPs of one token's forward pass on this rank."""
    total = 2 * _stream(config) * config["vocab_size"]
    for layer in layers(config):
        if layer["kind"] == "kda":
            total += kda_flops_per_token(config, executed)
        elif layer["kind"] == "latent":
            total += latent_flops_per_token(config, layer, executed)
        elif layer["kind"] == "dense":
            total += dense_flops_per_token(config)
        else:
            total += sum(sparse_parts_per_token(config, executed).values())
    return total


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)


def rule_cost(config: dict, width: int = 2) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` ONE layer's rule needs in one train step on one
    chip, forward and backward, whatever implements it.  Operations: the
    chunked forward's and twice that for their gradients.  Bytes, ``width``
    an element and 4 for ``beta`` and for ``g`` — which is a float32 A
    CHANNEL here, ``H d_k`` of them a token: the forward reads ``q``, ``k``,
    ``v``, ``beta``, ``g`` and writes ``o``; the backward reads those five
    and ``do`` and writes ``dq``, ``dk``, ``dv``, ``dbeta``, ``dg``.  No
    decayed product, no solved transform, no chunk state and nothing
    recomputed is credited: a fused kernel keeps them on the chip."""
    tokens = config["train_batch_size"] * config["sequence_length"]
    h, dk, dv = (config["kda_heads"], config["kda_key_features"],
                 config["kda_value_features"])
    forward = (2 * h * dk + 2 * h * dv) * width + (h + h * dk) * 4
    backward = (4 * h * dk + 3 * h * dv) * width + 2 * (h + h * dk) * 4
    return 3 * rule_flops_per_token(config) * tokens, \
        (forward + backward) * tokens


#: flash kernel (without ``_causal`` and the trailing number) -> (matmuls a
#: pair at the KEY's width, at the VALUE's width; tensors read or written at
#: the key's width, at the value's): forward ``q k^T`` | ``p v``, reading q,
#: k | v and writing o; the fused backward ``q k^T``, ``ds k``, ``ds^T q`` |
#: ``do v^T``, ``p^T do`` over q, k, dq, dk | v, do, dv; the split pair dq
#: (q, k, dq | v, do) and dk/dv (q, k, dk | v, do, dv)
FLASH = {"flash_fwd": (1, 1, 2, 2), "flash_bwd_fused": (3, 2, 4, 3),
         "flash_bwd_dq": (2, 1, 3, 2), "flash_bwd_dkv": (2, 2, 3, 3)}


def _latent_shape(config: dict) -> dict:
    found = {(layer["heads"], layer["latent"], layer["shared"])
             for layer in layers(config) if layer["kind"] == "latent"}
    if len(found) != 1:
        raise KeyError(f"latent attention layers of {len(found)} shapes: "
                       f"{sorted(found)}")
    (heads, latent, shared), = found
    return {"heads": heads, "latent": latent, "shared": shared}


def flash_cost(kind: str, config: dict, width: int = 2
               ) -> typing.Tuple[float, float]:
    """``(flops, bytes)`` of one call of the causal flash kernel the trace
    names ``kind`` at the latent attention layer's two widths (key
    ``features_per_head + shared_key``, value ``features_per_head``) over the
    lower triangle.  Operations: the kernel's matmuls, ``2 x width`` a pair
    and head each.  Bytes, ``width`` an element: each tensor once at its own
    width — the key at the kernel's, repeated shared part and all, since
    that is the call's operand — plus the float32 row statistics."""
    if not kind.endswith("_causal"):
        raise KeyError(f"kernel {kind!r}: only causal calls are costed")
    base = kind[:-len("_causal")]
    if base not in FLASH:
        raise KeyError(f"no cost function for kernel {kind!r}")
    shape = _latent_shape(config)
    at_key, at_value, key_tensors, value_tensors = FLASH[base]
    b, s, dv = config["train_batch_size"], config["sequence_length"], \
        config["features_per_head"]
    dk, q = dv + shape["shared"], shape["heads"]
    return ((at_key * 2 * dk + at_value * 2 * dv) * b * q
            * costs.causal_pairs(s),
            (key_tensors * dk + value_tensors * dv) * b * q * s * width
            + 2 * b * q * s * 4)
