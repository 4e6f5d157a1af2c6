"""Plain reference of MiniCPM-SALA (``minicpm_sala``): a pre-norm residual
stream in which block-selected sparse attention layers (``minicpm4``) and
lightning linear-attention layers (``lightning-attn``) alternate as
``mixer_types`` says, each followed by a dense SwiGLU MLP; RMSNorm, MiniCPM's
three multipliers, an embedding and a head of their own.

Written from the layer equations as ISSUE 46 states them (the published
``config.json``, Lightning Attention-2's recurrence, MiniCPM4 / InfLLM-V2's
block selection) in float32 ``jax.numpy`` under ``highest`` matmul precision.
The linear rule is run AS the recurrence: a ``lax.fori_loop`` over the
positions updating a ``[d, d]`` state a head — no chunks.  The selection is by
brute force: every pooled key gathered and averaged, every (pooled window,
block) overlap read off the two intervals, a stable sort of a query's block
scores; attention is explicit einsums under the mask the selection gives, one
block of queries at a time against all keys; the MLP and the logits go in
blocks of positions, the logits as a HOST array.  Parameters are read by the
names the program gives them — the seeded weights have to be the same ones —
and nothing else is taken from it.

The model may be given ONE tensor-parallel rank's share (the cell's): the
query and K/V heads a sparse layer's flags count (``q_heads<n>-kv_heads<m>``)
and ``lightning_heads_held`` lightning heads from ``lightning_heads_first``
are all this reference sees of a layer, and their part of ``W_o``'s sum is
what goes on to the stream, as in the program; nothing stands in for the
other rank or its all-reduce.

With ``E`` the embedding, ``h = 12 E[tokens]`` (``scale_emb``), per layer

    h = h + c mixer(rms(h) * w1)         c = scale_depth / sqrt(32 layers)
    h = h + c mlp(rms(h) * w2)           = 1.4 / sqrt(32), of the WHOLE model

``logits = W_head (rms(h) * wf / 16)`` (``hidden_size / dim_model_base``),
``rms(x) = x / sqrt(mean(x^2) + 1e-6)``.

Lightning layer on ``x [b, s, 4096]``, heads of 128:

    q, k, v, z = x W_q, x W_k, x W_v, x W_z
    q, k = rms_128(q) * w_q, rms_128(k) * w_k        a head (``qk_norm``)
    q, k = rotary(q), rotary(k)                      theta 10,000, all 128
    S_t = lambda_h S_{t-1} + k_t^T v_t;  o_t = q_t S_t / sqrt(128)
    y = rms_2048(o) * w_n * sigmoid(z)               over a GROUP of 16 heads'
                                                     outputs (2 groups a
                                                     layer), then the gate
    out = y W_o
    lambda_h = exp(-2^(-8 (h + 1) / 32)), h the head's index in the WHOLE
    layer

Sparse layer: ``q = rms_128(x W_q) * w_q``, ``k = rms_128(x W_k) * w_k``, ``v
= x W_v``, ``z = x W_z``, no positions; up to ``sparse_dense_length`` keys the
causal ``softmax(q k^T / sqrt(128)) v``; past it, for query ``t`` and the K/V
group ``g`` of its head:

    Kbar_j = mean(k_g[16 j : 16 j + 32]),  visible when 16 j + 32 <= t + 1
    p_(t,h,.) = softmax_j(q_(t,h) . Kbar_j / sqrt(128)) over the visible ones
    P_(t,g,j) = sum of p over the group's heads
    score(t, g, b) = max of P over the windows that overlap keys 64 b .. 64 b
                     + 63; +inf for block 0 and the 32 blocks that end at
                     t's own; -inf past t's own
    the 64 best blocks are kept (ties to the lower block), and
    o_(t,h) = softmax over the keys <= t of the kept blocks, times v

then ``(o * sigmoid(z)) W_o``.  No gradient flows through the selection.

Departures from the published models, each the program's too and each under
``assumed`` in ``benchmark/configs/minicpm_sala.json``:
- the softmax over the pooled keys is exact; InfLLM-V2 approximates its
  normaliser from a coarser pooling.
- the loss is the mean cross-entropy over all positions of the batch
  (``common.loss_of``); HF shifts labels itself and ignores an index.
- the gates and their products are float32.
- the lightning layers' output norm is over a group of 16 heads, not a head:
  a norm a head is ``sign(q_0 . k_0) v_0 / rms(v_0)`` at the first position,
  discontinuous in the activations (``model/lightning.py``'s docstring).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import common

SPARSE = {
    "w1": "norm_0/normal_var0",
    "w_key": "attention_0/normal_var0", "w_query": "attention_0/normal_var1",
    "w_value": "attention_0/normal_var2", "w_gate": "attention_0/normal_var3",
    "scale_query": "attention_0/normal_var4",
    "scale_key": "attention_0/normal_var5",
    "w_out": "attention_0/normal_var6",
}
LIGHTNING = {
    "w1": "norm_0/normal_var0",
    "w_query": "lightning_0/normal_var0", "w_key": "lightning_0/normal_var1",
    "w_value": "lightning_0/normal_var2", "w_gate": "lightning_0/normal_var3",
    "scale_query": "lightning_0/constant_var0",
    "scale_key": "lightning_0/constant_var1",
    "scale_out": "lightning_0/constant_var2",
    "w_out": "lightning_0/normal_var4",
}
MLP = {
    "w2": "norm_0/normal_var0",
    "w_gate": "mlp_0/normal_var0", "w_up": "mlp_0/normal_var1",
    "w_down": "mlp_0/normal_var2",
}
#: queries a block of the attention, of the selection (its ``[queries,
#: pooled keys, blocks]`` float32 overlap maximum is 134 MB a group at 16k),
#: positions a block of the MLP and of the logits
QUERY_BLOCK = 512
SELECT_BLOCK = 128
TOKEN_BLOCK = 2048


def rms(x, scale, eps):
    """RMSNorm over ALL trailing axes that ``scale`` has."""
    axes = tuple(range(x.ndim - scale.ndim, x.ndim))
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=axes,
                                      keepdims=True) + eps) * scale


def rotate(x, theta: float):
    """Rotary positions, rotate-half over the whole head: feature ``i`` pairs
    with ``i + d / 2``, both turn by ``pos * theta ** (-2 i / d)``."""
    s, d = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def decays(config) -> np.ndarray:
    """``lambda_h`` of the heads this share holds."""
    heads = int(config["lightning_heads"])
    held = int(config["lightning_heads_held"]) or heads
    index = int(config["lightning_heads_first"]) + np.arange(held)
    return np.exp(-np.exp2(-8.0 * (index + 1) / heads)).astype(np.float32)


def norm_group(config) -> int:
    """Heads the output norm normalises together."""
    return int(config["lightning_heads"]) \
        // int(config["lightning_norm_groups"])


def recurrence(q, k, v, lam):
    """``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``, position by
    position: ``q``, ``k``, ``v`` ``[b, s, h, d]``, ``lam [h]``."""
    bsz, s, h, d = q.shape

    def step(t, carry):
        state, out = carry                                   # [b, h, d, d]
        state = state * lam[None, :, None, None] \
            + k[:, t][..., :, None] * v[:, t][..., None, :]
        return state, out.at[:, t].set(
            jnp.einsum("bhd,bhde->bhe", q[:, t], state))

    return jax.lax.fori_loop(0, s, step, (
        jnp.zeros((bsz, h, d, d), jnp.float32), jnp.zeros_like(v)))[1]


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
@common.highest
def lightning_block(p, h, lam, theta: float, eps: float, group: int):
    x = rms(h, p["w1"], eps)
    q, k, v, z = (jnp.einsum("bsgf,gfo->bso", x, p[w])
                  for w in ("w_query", "w_key", "w_value", "w_gate"))
    d = p["scale_query"].shape[0]
    heads = lambda t: t.reshape(t.shape[:2] + (-1, d))          # noqa: E731
    q = rotate(rms(heads(q), p["scale_query"], eps), theta)
    k = rotate(rms(heads(k), p["scale_key"], eps), theta)
    o = recurrence(q, k, heads(v), lam) * d ** -0.5
    grouped = o.reshape(o.shape[:2] + (-1, group * d))
    normed = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), axis=-1, keepdims=True) + eps)
    y = normed.reshape(z.shape) * p["scale_out"] * jax.nn.sigmoid(z)
    return jnp.einsum("bsi,igf->bsgf", y, p["w_out"])


@functools.partial(jax.jit, static_argnums=(2,))
@common.highest
def _qkvz(p, h, eps: float):
    x = rms(h, p["w1"], eps)
    q = rms(jnp.einsum("bsgf,gfhd->bshd", x, p["w_query"]),
            p["scale_query"], eps)
    k = rms(jnp.einsum("bsgf,gfhd->bshd", x, p["w_key"]),
            p["scale_key"], eps)
    return (q, k, jnp.einsum("bsgf,gfhd->bshd", x, p["w_value"]),
            jnp.einsum("bsgf,gfhd->bshd", x, p["w_gate"]))


def overlap(pooled: int, blocks: int, sizes) -> np.ndarray:
    """``[pooled, blocks]``: pooled window ``j`` (keys ``stride j .. stride j
    + kernel - 1``) shares a key with block ``b``."""
    first = sizes["stride"] * np.arange(pooled)[:, None]
    start = sizes["block"] * np.arange(blocks)[None, :]
    return (first <= start + sizes["block"] - 1) \
        & (first + sizes["kernel"] - 1 >= start)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
@common.highest
def _select(q_block, pooled_keys, first, kernel, stride, block, topk,
            init_blocks, window):
    """``keep [b, g, queries, blocks]`` of one block of queries at positions
    ``first ..``: ``q_block [b, n, h, d]``, ``pooled_keys [b, j, g, d]``."""
    b, n, h, d = q_block.shape
    pooled, g = pooled_keys.shape[1], pooled_keys.shape[2]
    blocks = (stride * (pooled - 1) + kernel) // block
    pos = first + jnp.arange(n)
    logits = jnp.einsum("bngrd,bjgd->bgrnj",
                        q_block.reshape(b, n, g, h // g, d), pooled_keys) \
        * d ** -0.5
    visible = (stride * jnp.arange(pooled) + kernel)[None, :] \
        <= pos[:, None] + 1
    probs = jax.nn.softmax(jnp.where(visible, logits, -jnp.inf), axis=-1)
    # a query that sees no whole window yet: softmax of all -inf is nan
    total = jnp.sum(jnp.where(visible, probs, 0.0), axis=2)  # [b, g, n, j]
    meets = jnp.asarray(overlap(pooled, blocks, {
        "kernel": kernel, "stride": stride, "block": block}))
    score = jnp.max(jnp.where(meets[None, None, None], total[..., None],
                              -jnp.inf), axis=-2)             # [b, g, n, blocks]
    idx = jnp.arange(blocks)[None, :]
    own = (pos // block)[:, None]
    forced = (idx < init_blocks) | ((idx > own - max(1, window // block))
                                    & (idx <= own))
    score = jnp.where(forced, jnp.inf, score)
    score = jnp.where(idx <= own, score, -jnp.inf)
    order = jnp.argsort(-score, axis=-1, stable=True)[..., :topk]
    chosen = jnp.zeros(score.shape, bool)
    chosen = jnp.put_along_axis(chosen, order, True, axis=-1, inplace=False)
    return chosen & (score > -jnp.inf)


@functools.partial(jax.jit, static_argnums=(5,))
@common.highest
def _attend(q_block, k, v, keep, first, block: int):
    """One block of queries, positions ``first ..``, against all keys; ``keep
    [b, g, queries, blocks]`` or None (every block)."""
    b, n, h, d = q_block.shape
    g = k.shape[2]
    score = jnp.einsum("bngrd,btgd->bgrnt",
                       q_block.reshape(b, n, g, h // g, d), k) * d ** -0.5
    seen = ((first + jnp.arange(n))[:, None]
            >= jnp.arange(k.shape[1])[None, :])[None, None, None]
    if keep is not None:
        seen = seen & jnp.repeat(keep, block, axis=-1)[:, :, None]
    weight = jax.nn.softmax(jnp.where(seen, score, -jnp.inf), axis=-1)
    return jnp.einsum("bgrnt,btgd->bngrd", weight, v).reshape(b, n, h, d)


@jax.jit
@common.highest
def _gate_out(o, z, w_out):
    return jnp.einsum("bshd,hdgf->bsgf", o * jax.nn.sigmoid(z), w_out)


def sparse_sizes(config) -> dict:
    return {short: int(config[f"sparse_{key}"]) for short, key in (
        ("kernel", "kernel_size"), ("stride", "kernel_stride"),
        ("block", "block_size"), ("topk", "topk"),
        ("init_blocks", "init_blocks"), ("window", "window"),
        ("dense_length", "dense_length"))}


def selection(q, k, sizes) -> jnp.ndarray:
    """``keep [b, g, s, s / block]`` of a whole layer, by brute force."""
    s = q.shape[1]
    pooled = (s - sizes["kernel"]) // sizes["stride"] + 1
    rows = sizes["stride"] * np.arange(pooled)[:, None] \
        + np.arange(sizes["kernel"])[None, :]
    pooled_keys = jnp.mean(k[:, rows], axis=2)                # [b, j, g, d]
    n = min(s, SELECT_BLOCK)
    return jnp.concatenate([
        _select(q[:, i:i + n], pooled_keys, i, sizes["kernel"],
                sizes["stride"], sizes["block"], sizes["topk"],
                sizes["init_blocks"], sizes["window"])
        for i in range(0, s, n)], axis=2)


def sparse_block(p, h, sizes, eps: float, keep_out=None):
    q, k, v, z = _qkvz(p, h, eps)
    s = q.shape[1]
    keep = selection(q, k, sizes) if s > sizes["dense_length"] else None
    if keep_out is not None:
        keep_out.append(keep)
    n = min(s, QUERY_BLOCK)
    o = jnp.concatenate([
        _attend(q[:, i:i + n], k, v,
                None if keep is None else keep[:, :, i:i + n], i,
                sizes["block"])
        for i in range(0, s, n)], axis=1)
    return _gate_out(o, z, p["w_out"])


@functools.partial(jax.jit, static_argnums=(2,))
@common.highest
def _mlp(p, h, eps: float):
    x = rms(h, p["w2"], eps)
    gate = jnp.einsum("bsgf,gfi->bsi", x, p["w_gate"])
    up = jnp.einsum("bsgf,gfi->bsi", x, p["w_up"])
    return jnp.einsum("bsi,igf->bsgf", jax.nn.silu(gate) * up, p["w_down"])


def mlp_block(p, h, eps: float):
    n = min(h.shape[1], TOKEN_BLOCK)
    return jnp.concatenate([_mlp(p, h[:, i:i + n], eps)
                            for i in range(0, h.shape[1], n)], axis=1)


@functools.partial(jax.jit, static_argnums=(3, 4))
@common.highest
def _logits(h, scale, w_head, eps: float, divisor: float):
    return jnp.einsum("bsgf,gfv->bsv", rms(h, scale, eps) / divisor, w_head)


def mixers(config):
    """The kind of each layer's mixer in one depth unit, read off
    ``block_config``: every even block is a mixer's, every odd one its
    MLP's, each after its norm."""
    kinds = [block["layer"][1].split("-")[0]
             for block in config["block_config"]]
    assert all(block["layer"][0] == "norm-rms-scale"
               for block in config["block_config"]), config["block_config"]
    assert all(k == "mlp" for k in kinds[1::2]), kinds
    assert set(kinds[0::2]) <= {"attention", "lightning"}, kinds
    return kinds[0::2]


def forward(variables, tokens, config, stream_dtype=None, keep_out=None):
    """Logits ``[b, s, vocab]`` (float32, a host array) for ``tokens [b,
    s]``.  ``stream_dtype``: round the residual stream to it after the
    embedding and after every block — not the model, but what a lower
    activation precision than the configuration's does to it.  ``keep_out``:
    a list that receives every sparse layer's selection (None for a dense
    one)."""
    def stream(h):
        return h if stream_dtype is None \
            else h.astype(stream_dtype).astype(jnp.float32)

    eps = float(config["norm_epsilon"])
    joins = float(config["residual_multiplier"])
    sizes = sparse_sizes(config)
    lam = jnp.asarray(decays(config))
    h = stream(float(config["embedding_multiplier"]) * common.param(
        variables, "input0/gather0/embed0/normal_var0")[tokens])
    for d in range(config["depth"]):
        for i, kind in enumerate(mixers(config)):
            if kind == "lightning":
                out = lightning_block(
                    common.block_params(variables, d, 2 * i, LIGHTNING), h,
                    lam, float(config["rope_theta"]), eps,
                    norm_group(config))
            else:
                out = sparse_block(
                    common.block_params(variables, d, 2 * i, SPARSE), h,
                    sizes, eps, keep_out)
            h = stream(h + joins * out)
            h = stream(h + joins * mlp_block(
                common.block_params(variables, d, 2 * i + 1, MLP), h, eps))
    final = common.param(variables, "output0/lang_out0_0/norm_0/normal_var0")
    head = common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :]
    n = min(h.shape[1], TOKEN_BLOCK)
    return np.concatenate([
        np.asarray(_logits(h[:, i:i + n], final, head, eps,
                           float(config["logits_scaling"])))
        for i in range(0, h.shape[1], n)], axis=1)


def train_loss(variables, tokens, targets, config):
    """What the step differentiates: the mean cross-entropy of ``forward``'s
    logits (on device, no host blocks: the tests' sizes)."""
    eps = float(config["norm_epsilon"])
    joins = float(config["residual_multiplier"])
    sizes = sparse_sizes(config)
    lam = jnp.asarray(decays(config))
    h = float(config["embedding_multiplier"]) * common.param(
        variables, "input0/gather0/embed0/normal_var0")[tokens]
    for d in range(config["depth"]):
        for i, kind in enumerate(mixers(config)):
            p = common.block_params(variables, d, 2 * i,
                                    LIGHTNING if kind == "lightning"
                                    else SPARSE)
            h = h + joins * (
                lightning_block(p, h, lam, float(config["rope_theta"]), eps,
                                norm_group(config))
                if kind == "lightning" else sparse_block(p, h, sizes, eps))
            h = h + joins * mlp_block(
                common.block_params(variables, d, 2 * i + 1, MLP), h, eps)
    final = common.param(variables, "output0/lang_out0_0/norm_0/normal_var0")
    head = common.param(variables, "output0/embed0/normal_var0")[:, :, 0, :]
    logits = _logits(h, final, head, eps, float(config["logits_scaling"]))
    return common.loss_of(logits, targets, float(config["z_loss"]))
