"""Operations and bytes the algorithms need, computed from shapes.

Kept with the benchmark so that no later change to the program moves the
yardstick: the kernels' costs per call (keyed by the kernel's name in the
device trace), the model's required FLOPs per token, and the table of
peaks.  "Required" means what the mathematics needs — a causal mixer needs
the lower triangle only, and nothing recomputed is counted.

Axes: ``b`` sequences, ``s`` positions, ``h`` heads, ``k`` features per
head, all as held by ONE chip.
"""
from __future__ import annotations

import json
import os
import typing

HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDeviceKind(LookupError):
    pass


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDeviceKind(
            f"no peaks for device kind {device_kind!r} in "
            f"benchmark/roofline/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def causal_pairs(s: int) -> int:
    """Query-key pairs of one causal sequence: the lower triangle with its
    diagonal."""
    return s * (s + 1) // 2


# ---- kernels: (flops, bytes) of ONE call ------------------------------------

def _map_mixer(b, s, h, k, width: int):
    """``out[b,s,h,k] = sum_{t<=s} map[h,s,t] * v[b,t,h,k]`` and its two
    gradients are each one triangular matmul of the same size; each reads
    two operands and writes one (activations ``b*s*h*k``, the map's lower
    triangle ``h * pairs``), ``width`` bytes an element."""
    pairs = causal_pairs(s)
    flops = 2 * b * h * k * pairs
    act, tri = b * s * h * k * width, h * pairs * width
    return flops, act, tri


def map_mixer_fwd(b, s, h, k, width=2):
    flops, act, tri = _map_mixer(b, s, h, k, width)
    return flops, 2 * act + tri


def map_mixer_bwd_dval(b, s, h, k, width=2):
    return map_mixer_fwd(b, s, h, k, width)


def map_mixer_bwd_dbias(b, s, h, k, width=2):
    flops, act, tri = _map_mixer(b, s, h, k, width)
    return flops, 2 * act + tri


def _flash(b, s, h, k, matmuls: int, tensors: int, width: int):
    """Causal attention: every matmul (scores, weighted values, and in the
    backward their three gradients plus the recomputed scores and dP) costs
    ``2 * k`` per query-key pair; ``tensors`` activations of ``b*s*h*k`` are
    read or written, plus the float32 row statistics."""
    flops = matmuls * 2 * b * h * k * causal_pairs(s)
    return flops, tensors * b * s * h * k * width + 2 * b * h * s * 4


def flash_fwd(b, s, h, k, width=2):
    return _flash(b, s, h, k, 2, 4, width)          # q k v -> o


def flash_bwd_fused(b, s, h, k, width=2):
    return _flash(b, s, h, k, 5, 8, width)          # q k v o do -> dq dk dv


def flash_bwd_dq(b, s, h, k, width=2):
    return _flash(b, s, h, k, 3, 5, width)          # S, dP, dQ


def flash_bwd_dkv(b, s, h, k, width=2):
    return _flash(b, s, h, k, 4, 6, width)          # S, dP, dV, dK


#: kernel name in the trace (without ``_causal`` and the trailing number)
#: -> cost of one call
KERNELS: typing.Dict[str, typing.Callable] = {
    "map_mixer_fwd": map_mixer_fwd,
    "map_mixer_bwd_dval": map_mixer_bwd_dval,
    "map_mixer_bwd_dbias": map_mixer_bwd_dbias,
    "flash_fwd": flash_fwd,
    "flash_bwd_fused": flash_bwd_fused,
    "flash_bwd_dq": flash_bwd_dq,
    "flash_bwd_dkv": flash_bwd_dkv,
}


def kernel_cost(kind: str, b: int, s: int, h: int, k: int
                ) -> typing.Tuple[int, int]:
    """``(flops, bytes)`` of one call of the kernel the trace names
    ``kind``.  Only causal calls are costed: both configurations mix
    causally, and a full-square call would need other counts."""
    if not kind.endswith("_causal"):
        raise KeyError(f"kernel {kind!r}: only causal calls are costed")
    base = kind[:-len("_causal")]
    if base not in KERNELS:
        raise KeyError(f"no cost function for kernel {kind!r}")
    return KERNELS[base](b, s, h, k)


def least_seconds(flops: float, bytes_: float, peak: dict
                  ) -> typing.Tuple[float, str]:
    """The least time the chip could take, and which peak sets it."""
    compute = flops / peak["bf16_flops_per_s"]
    memory = bytes_ / peak["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


# ---- the model: required forward FLOPs per token ----------------------------

def _mixing_keys(s: int, mixing: str) -> float:
    """Keys one query meets on average: ``causal`` is what the mathematics
    needs, ``square`` what a dense masked matmul executes (used only to
    check this enumeration against a jaxpr count of the plain reference)."""
    return {"causal": (s + 1) / 2, "square": float(s)}[mixing]


def forward_flops_per_token(config: dict, mixing: str = "causal") -> float:
    """Matmul FLOPs of one token's forward pass, from the layer equations
    in ``benchmark/reference``.  The token gather is a lookup and costs
    nothing; norms and activations are not matmuls."""
    h, k, s = config["heads"], config["features_per_head"], \
        config["sequence_length"]
    d = h * k
    i = int(d * config["group_linear_factor"]
            * config["intermediate_feed_forward_multiplier_multiplier"] / h)
    j = k * config["group_linear_factor"]
    narrow = int(i * config.get("vocab_weight_factorization", 0.125))
    keys = _mixing_keys(s, mixing)
    group_linear = 2 * d * i + 2 * i * h * j + 2 * h * j * k
    per_depth = group_linear
    for layer in config["block_config"][1]["layer"]:
        name, *flags = layer.split("-")
        if name != "attention":
            continue
        if "dot_product" in flags:
            # bottleneck in, key/query/value out, scores and weighted values
            per_depth += 2 * d * i + 3 * 2 * i * d + 2 * 2 * d * keys
        else:
            per_depth += 2 * d * keys                   # one learned map
    return 2 * narrow * d + config["depth"] * per_depth \
        + 2 * d * config["vocab_size"]


def train_flops_per_token(config: dict) -> float:
    """Forward + backward = 3 x forward; recomputation is not credited."""
    return 3.0 * forward_flops_per_token(config)
