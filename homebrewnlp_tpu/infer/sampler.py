"""Autoregressive sampling.

Reference: /root/reference/src/run/inference.py — an mtf.while_loop whose body
rebuilds the ENTIRE forward model every token (no KV cache; an MTF artifact).
This implementation keeps the same sampling semantics — gumbel noise scaled by
``sampling_temperature`` added to logits (inference.py:88-92), shift-by-one,
positional one-hot update, start at ``initial_autoregressive_position`` — as a
``lax.while_loop``.  The full-forward-per-token structure is preserved for
exact output parity (the mixer attention reads the whole prefix through a
learned map, so generic layer stacks can't assume causal streaming state);
jit compiles the body once, unlike MTF which unrolled compile per shape.
"""
from __future__ import annotations

import threading
import time
import typing

import jax
import jax.numpy as jnp
import numpy as np

from ..config import ModelParameter
from ..model import Model

#: decode-progress hook (docs/OBSERVABILITY.md 'Cost attribution'): when
#: set, the STEPPED decode loop reports ``hook("chunk", dt=..., steps=...,
#: cache_bytes=...)`` after each donated chunk completes and
#: ``hook("first_token", rows=[...])`` as each batch row's first generated
#: token comes to exist (per-row: co-batched prompts of different lengths
#: fire in different chunks) — the
#: serving layer (infer/rest_api.py) turns these into TTFT / ITL /
#: cache-bandwidth metrics.  None (the default) keeps this module free of
#: telemetry: no clock reads, no per-chunk device sync.
#: per-THREAD hook storage: the installer thread is always the thread that
#: runs the decode (device loop in isolated serving, the handler thread
#: in-process), and in-process servers run handlers concurrently — a
#: process-global here would let overlapping requests swap each other's
#: hooks mid-decode and leak a stale one on exit
_DECODE_PROGRESS = threading.local()


def decode_progress_hook() -> typing.Optional[typing.Callable]:
    """The calling thread's decode-progress hook (None outside serving)."""
    return getattr(_DECODE_PROGRESS, "hook", None)


def set_decode_progress_hook(hook: typing.Optional[typing.Callable]
                             ) -> typing.Optional[typing.Callable]:
    """Install the calling thread's decode-progress hook; returns the
    PREVIOUS hook so callers can restore it (the serving path installs per
    decode call)."""
    prev = decode_progress_hook()
    _DECODE_PROGRESS.hook = hook
    return prev


def _repetition_penalty(logits, seen, rep):
    """HF-convention repetition penalty: tokens that already appeared
    (``seen`` [batch, vocab] counts > 0) have positive logits divided by
    ``rep`` and negative logits multiplied by it — both push the
    probability down for rep > 1.  rep == 1 is identity."""
    bdim = (slice(None),) + (None,) * (logits.ndim - 2)
    r = rep[bdim + (None,)]
    appeared = seen[:, None, None, :] > 0          # logits are [b, ., tp, v]
    penalized = jnp.where(logits > 0, logits / r, logits * r)
    return jnp.where(appeared, penalized, logits)


def _filter_logits(logits, tb, top_k, top_p):
    """Top-k / nucleus (top-p) filtering, HuggingFace convention: the
    distribution is softmax(logits / T) (our gumbel draw at scale T samples
    exactly that), tokens outside the allowed set drop to -1e30.  Per-row
    ``top_k`` int32 [batch] (<=0 disables) and ``top_p`` f32 [batch]
    (>=1 disables); the argmax token is always kept, so greedy rows are
    unaffected.  Beyond-reference serving surface — the reference samples
    the full distribution only (src/run/inference.py:88-92)."""
    v = logits.shape[-1]
    bdim = (slice(None),) + (None,) * (logits.ndim - 2)
    scaled = logits / jnp.maximum(tb, 1e-6)[bdim + (None,)]
    srt = jnp.sort(scaled, axis=-1)[..., ::-1]           # descending
    k_eff = jnp.where((top_k <= 0) | (top_k > v), v, top_k)[bdim + (None,)]
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # sequential top-k THEN nucleus, both in sorted space: the nucleus mass
    # renormalizes over the top-k survivors (HF TopK->TopP warper order),
    # whose total mass is cum at position k_eff-1
    mass_k = jnp.take_along_axis(cum, (k_eff - 1).astype(jnp.int32)
                                 * jnp.ones_like(cum, jnp.int32)[..., :1],
                                 axis=-1)
    pos = jnp.arange(v)
    keep_sorted = ((cum - probs) < top_p[bdim + (None,)] * mass_k) \
        & (pos < k_eff)
    # the crossing token is included and the set is never empty (top_p=0
    # keeps exactly the argmax)
    nkeep = jnp.maximum(keep_sorted.sum(-1, keepdims=True), 1)
    pth = jnp.take_along_axis(srt, nkeep - 1, axis=-1)
    return jnp.where(scaled >= pth, logits, -1e30)


def make_sampler(model: Model, mesh=None,
                 logits_filter: bool = False) -> typing.Callable:
    """Returns jit-able sample(variables, token_x, token_y, initial_pos,
    temperature, end_iterations, key) -> tokens [batch, seq, patch].

    ``mesh``: serving mesh (core/sharding.py ``inference_mesh``) — the
    forward runs with the training layout rules (batch over 'data', heads
    over 'model'), the reference's inference-through-the-training-mesh
    design (/root/reference/src/run/run.py:200-308)."""
    params: ModelParameter = model.params

    def sample(variables, token_x, token_y, initial_pos, temperature,
               end_iterations, key, top_k=None, top_p=None, rep_penalty=None):
        seq_axis = 1
        batch = token_x.shape[0]
        # per-row prompt lengths / temperatures (batched serving); scalars
        # broadcast — the loop then starts at the smallest prompt end and a
        # row guard keeps longer prompts untouched until their own start
        ipb = jnp.broadcast_to(jnp.asarray(initial_pos, jnp.int32), (batch,))
        tb = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (batch,))
        if logits_filter:
            kb = jnp.broadcast_to(jnp.asarray(
                0 if top_k is None else top_k, jnp.int32), (batch,))
            pb = jnp.broadcast_to(jnp.asarray(
                1.0 if top_p is None else top_p, jnp.float32), (batch,))
            rb = jnp.broadcast_to(jnp.asarray(
                1.0 if rep_penalty is None else rep_penalty, jnp.float32),
                (batch,))

        def cond_fn(state):
            position, *_ = state
            return position < end_iterations

        def body_fn(state):
            position, token_x, key = state
            info = model.apply(variables, {"token_x": token_x,
                                           "token_y": token_y}, mesh=mesh)
            logits = info.token_out.data.astype(jnp.float32)  # [b, s, tp, v]
            if logits_filter:
                # repetition penalty over the context BEFORE the write
                # position (prompt + tokens generated so far)
                vocab = model.params.vocab_size
                rows = jnp.arange(batch)[:, None, None]
                cmask = (jnp.arange(token_x.shape[1])[None, :, None]
                         < position).astype(jnp.float32)
                seen = jnp.zeros((batch, vocab), jnp.float32
                                 ).at[rows, token_x].add(cmask)
                logits = _repetition_penalty(logits, seen, rb)
                logits = _filter_logits(logits, tb, kb, pb)
            key, sub = jax.random.split(key)
            u = jax.random.uniform(sub, logits.shape, jnp.float32,
                                   minval=1e-9, maxval=1.0)
            logits = logits + jnp.log(-jnp.log(u)) * (-tb[:, None, None, None])
            tokens = jnp.argmax(logits, axis=-1)                 # [b, s, tp]
            # shift(+1): the prediction made at p-1 fills position p
            tokens = jnp.roll(tokens, 1, axis=seq_axis)
            tokens = tokens.at[:, 0].set(0)
            onehot = (jnp.arange(token_x.shape[seq_axis]) == position
                      ).astype(token_x.dtype)[None, :, None]
            onehot = onehot * (position >= ipb[:, None, None]).astype(onehot.dtype)
            token_x = (tokens * onehot + token_x * (1 - onehot)).astype(token_x.dtype)
            return position + 1, token_x, key

        position = jnp.min(ipb)
        _, token_x, _ = jax.lax.while_loop(cond_fn, body_fn,
                                           (position, token_x, key))
        return token_x

    return sample


def decode_cache_shapes(model: Model, variables, token_x) -> dict:
    """Cache pytree STRUCTURE for ``make_kv_sampler`` (discovered abstractly
    via eval_shape — no device compute; callable at trace time).

    When the decode scan engages, the caches are DEPTH-STACKED
    (``model.blocks.stack_decode_caches``) so the sampler's loop carry feeds
    the scan directly (read as invariants, row updates as ys) — the
    per-token flat<->stacked restack was hundreds of MB of HBM traffic per
    token at flagship size (docs/PERFORMANCE.md 'Decoding').  Falls back to
    the flat layout when a stacked carry wouldn't round-trip (e.g.
    non-homogeneous stacks where the decode body unrolls and resolves flat
    names)."""
    from ..model import blocks as blocks_mod

    tok0 = token_x[:, :1]
    shapes = jax.eval_shape(
        lambda v, t: model.apply_decode(v, t, jnp.int32(0), {})[1],
        variables, tok0)
    # abstract stacking: eval_shape lets jnp.stack run on shape structs
    stacked = jax.eval_shape(
        lambda f: blocks_mod.stack_decode_caches(model.params, f),
        dict(shapes))
    if not any(k.startswith(blocks_mod.STACKED_CACHE_PREFIX) for k in stacked):
        return dict(shapes)
    try:
        out_shapes = jax.eval_shape(
            lambda v, t, c: model.apply_decode(v, t, jnp.int32(0), c)[1],
            variables, tok0, stacked)
    except (TypeError, ValueError, KeyError) as e:
        # structural mismatch only — anything else is a real model bug and
        # must surface.  The flat fallback restacks per token (slow); warn so
        # the perf regression is observable.
        import warnings
        warnings.warn(f"stacked decode-cache probe failed ({e!r}); "
                      "falling back to the flat (slower) cache layout")
        return dict(shapes)
    same_structure = (set(out_shapes) == set(stacked)
                      and all(out_shapes[k].shape == tuple(stacked[k].shape)
                              for k in stacked))
    return stacked if same_structure else dict(shapes)


def init_decode_caches(model: Model, variables, token_x) -> dict:
    """Zero-filled cache pytree (materialised ``decode_cache_shapes``).

    Prefer passing ``caches=None`` to the sampler: it then builds the zeros
    INSIDE the jitted computation, so no host-side cache allocation exists —
    passing multi-GB zero buffers as jit arguments kept a second, unusable
    donated copy live (what pushed flagship batch-32 decoding out of HBM)."""
    return {k: jnp.zeros(v.shape, v.dtype)
            for k, v in decode_cache_shapes(model, variables, token_x).items()}


def _match_cache_layout(model: Model, produced: dict, expected: dict) -> dict:
    """Re-layout prefill-produced caches (flat vs depth-stacked) to the
    structure the decode body's discovery pass expects, then hard-check
    shapes/dtypes — a silent mismatch would corrupt decode."""
    from ..model import blocks as blocks_mod
    params = model.params
    if set(produced) != set(expected):
        flat = blocks_mod.unstack_decode_caches(params, produced)
        if set(flat) == set(expected):
            produced = flat
        else:
            stacked = blocks_mod.stack_decode_caches(params, flat)
            if set(stacked) != set(expected):
                raise ValueError(
                    "prefill produced a cache structure the decode body "
                    f"does not expect: {sorted(set(produced) ^ set(expected))}")
            produced = stacked
    for k, v in expected.items():
        if produced[k].shape != tuple(v.shape) or produced[k].dtype != v.dtype:
            raise ValueError(f"prefill cache {k!r} is {produced[k].shape} "
                             f"{produced[k].dtype}, decode expects "
                             f"{tuple(v.shape)} {v.dtype}")
    return produced


def _kv_prep(model: Model, token_x, ipb, logits_filter: bool):
    """Pre-loop state shared by the fused and stepped KV paths: the
    full-sampler parity write at position 0, and the repetition-penalty
    ``seen`` counts seeded from each row's prompt region.

    Factored out so the stepped path (host loop over donated chunks) and the
    fused path (one while_loop) start from bit-identical state — greedy
    parity between the two is a tested invariant (tests/decode_inplace_test)."""
    # full-sampler parity: its first iteration at position 0 writes 0
    # (the roll fills index 0 with zeros)
    zero_first = (ipb == 0)[:, None]
    token_x = token_x.at[:, 0].set(
        jnp.where(zero_first, jnp.zeros_like(token_x[:, 0]), token_x[:, 0]))
    seen0 = None
    if logits_filter:
        # token-occurrence counts for the repetition penalty, seeded
        # from each row's prompt region and scatter-updated per step.
        # ipb == 0 rows still hold one context token: index 0 — the
        # zero_first write just above (which is why this runs AFTER it);
        # the full sampler counts it via cmask index < position from
        # position 1, so seed it here too
        batch = token_x.shape[0]
        vocab = model.params.vocab_size
        rows = jnp.arange(batch)[:, None, None]
        pmask = (jnp.arange(token_x.shape[1])[None, :, None]
                 < jnp.maximum(ipb, 1)[:, None, None]).astype(jnp.float32)
        seen0 = jnp.zeros((batch, vocab), jnp.float32
                          ).at[rows, token_x].add(pmask)
    return token_x, seen0


def _kv_body(model: Model, mesh, logits_filter: bool, variables, ipb, tb,
             filt):
    """One KV-cached decode step ``state -> state`` (state = (q, token_x,
    caches, key[, seen])).  The single definition serves the fused
    while_loop AND the donated stepped chunks — both walk the identical
    body, so their greedy outputs match exactly."""
    batch = ipb.shape[0]
    rows = jnp.arange(batch)[:, None, None]
    if logits_filter:
        kb, pb, rb = filt

    def body_fn(state):
        if logits_filter:
            q, token_x, caches, key, seen = state
        else:
            q, token_x, caches, key = state
        cur = jax.lax.dynamic_slice_in_dim(token_x, q, 1, axis=1)
        logits, caches = model.apply_decode(variables, cur, q, caches,
                                            mesh=mesh)
        # named-scope region: everything downstream of the model forward is
        # token SAMPLING (filters, gumbel, argmax, token write) — trace
        # attribution separates it from cache-read/cache-write and the model
        # body (docs/OBSERVABILITY.md 'Cost attribution')
        with jax.named_scope("sampling"):
            logits = logits.astype(jnp.float32)      # [b, 1, tp, v]
            if logits_filter:
                logits = _repetition_penalty(logits, seen, rb)
                logits = _filter_logits(logits, tb, kb, pb)
            key, sub = jax.random.split(key)
            u = jax.random.uniform(sub, logits.shape, jnp.float32,
                                   minval=1e-9, maxval=1.0)
            logits = logits + jnp.log(-jnp.log(u)) * (-tb[:, None, None, None])
            nxt = jnp.argmax(logits, axis=-1).astype(token_x.dtype)
            old = jax.lax.dynamic_slice_in_dim(token_x, q + 1, 1, axis=1)
            new = jnp.where(q + 1 >= ipb[:, None, None], nxt, old)
            token_x = jax.lax.dynamic_update_slice_in_dim(token_x, new, q + 1,
                                                          axis=1)
        if logits_filter:
            # count the newly WRITTEN token (prompt rows not yet at
            # their boundary keep `old`, already counted by seen0)
            seen = seen.at[rows, new].add(
                (q + 1 >= ipb).astype(jnp.float32)[:, None, None])
            return q + 1, token_x, caches, key, seen
        return q + 1, token_x, caches, key

    return body_fn


def make_kv_sampler(model: Model, mesh=None, prefill: bool = False,
                    logits_filter: bool = False) -> typing.Callable:
    """KV-cached sampler: O(1) compute per token via ``Model.apply_decode``.

    Replaces the reference's full-model-per-token while_loop
    (/root/reference/src/run/inference.py:76-97 — an MTF artifact, see
    SURVEY.md §7).  Greedy (temperature=0) output matches ``make_sampler``
    exactly; for temperature>0 the distribution is identical but the gumbel
    draw consumes [batch, 1, patch, vocab] noise per step instead of noise
    over the full sequence, so individual samples differ from the
    full-forward sampler's stream.

    Loop identity with the full sampler: its iteration at ``position`` writes
    token_x[position] from logits[position-1]; here step ``q`` consumes
    token_x[q] and writes q+1 (when q+1 >= initial_pos), walking q from 0 so
    caches fill causally through the prompt (prefill and decode share one
    loop).

    ``prefill=True`` replaces the per-token prompt walk with ONE full
    forward (``Model.apply_prefill``): the caches for steps
    ``0..min(initial_pos)-2`` are captured from the full-length pass (flash
    kernels and all) and the loop enters directly at the last prompt
    position — O(1) model calls to first generated token instead of
    O(prompt).  Greedy outputs are identical for float cache dtypes (the
    decode-parity invariant: causal layers).  With lossy caches
    (``decode_cache_dtype`` int8/bf16 below the calc dtype) prefill is
    near- but not bit-identical — the walk computes each position from the
    DEQUANTIZED history so its deeper activations carry compounded
    quantization error, while prefill captures from the exact forward;
    prefill's caches are the more faithful of the two.
    """
    def sample(variables, token_x, initial_pos, temperature, end_iterations,
               key, caches=None, top_k=None, top_p=None, rep_penalty=None):
        batch = token_x.shape[0]
        # per-row prompt lengths / temperatures (batched serving: each
        # concurrent request keeps its own boundary and noise scale);
        # scalars broadcast to the uniform single-request behaviour
        ipb = jnp.broadcast_to(jnp.asarray(initial_pos, jnp.int32), (batch,))
        tb = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (batch,))
        if logits_filter:
            kb = jnp.broadcast_to(jnp.asarray(
                0 if top_k is None else top_k, jnp.int32), (batch,))
            pb = jnp.broadcast_to(jnp.asarray(
                1.0 if top_p is None else top_p, jnp.float32), (batch,))
            rb = jnp.broadcast_to(jnp.asarray(
                1.0 if rep_penalty is None else rep_penalty, jnp.float32),
                (batch,))
        # iterations at position >= seq are no-ops in the full sampler (its
        # one-hot write misses); clamp instead of letting the update clamp
        end_iterations = jnp.minimum(end_iterations, token_x.shape[1])
        token_x, seen0 = _kv_prep(model, token_x, ipb, logits_filter)

        q_start = jnp.asarray(0, jnp.int32)
        if not caches:
            if prefill:
                # one full forward captures the caches decode steps
                # 0..n0-1 would write; the loop enters at q = n0 (the step
                # that consumes the last prompt token and emits the first
                # generated one).  Steps skipped this way write nothing:
                # step q writes q+1 only when q+1 >= ipb, and
                # q < n0 = min(ipb)-1 implies q+1 < min(ipb).
                n0 = jnp.maximum(jnp.min(ipb) - 1, 0)
                produced = model.apply_prefill(variables, token_x, n0,
                                               mesh=mesh)
                expected = decode_cache_shapes(model, variables, token_x)
                caches = _match_cache_layout(model, produced, expected)
                q_start = n0
            else:
                # build the zero caches INSIDE the trace: passing them as jit
                # arguments keeps an unusable donated copy live — 2x cache
                # HBM, which pushed flagship batch-32 decode out of memory
                caches = {k: jnp.zeros(v.shape, v.dtype) for k, v in
                          decode_cache_shapes(model, variables,
                                              token_x).items()}

        def cond_fn(state):
            q, *_ = state
            return q < end_iterations - 1

        body_fn = _kv_body(model, mesh, logits_filter, variables, ipb, tb,
                           (kb, pb, rb) if logits_filter else None)

        if logits_filter:
            _, token_x, _, _, _ = jax.lax.while_loop(
                cond_fn, body_fn, (q_start, token_x, caches, key, seen0))
        else:
            _, token_x, _, _ = jax.lax.while_loop(
                cond_fn, body_fn, (q_start, token_x, caches, key))
        return token_x

    return sample


def make_kv_step(model: Model, mesh=None, logits_filter: bool = False,
                 init_caches: bool = False) -> typing.Callable:
    """One CHUNK of KV-cached decode steps with a donatable carry.

    ``step(variables, ipb, tb, end_iterations, q_hi, fargs, carry)`` advances
    ``carry = (q, token_x, caches, key[, seen])`` until ``q`` reaches
    ``min(q_hi, end_iterations - 1)`` and returns the updated carry.  Jitted
    with the carry DONATED (``_jit_sampler`` kinds ``"kv_step"``), every
    cache buffer is pinned to an input_output_alias: the XLA while carry
    chains parameter -> loop state -> result, so the per-token cache scatter
    provably updates in place instead of copying the multi-GB cache — the
    property the fused single-while_loop sampler loses at large cache sizes
    (BASELINE.md round 5: 60.1 ms/token at 32k vs the ~8 ms read bound) and
    the one `analysis/hlo_lint.py` asserts on the compiled module.

    The body is ``_kv_body`` — the same step the fused sampler runs — so
    greedy outputs are bit-identical between the two loop structures.

    ``init_caches=True`` builds the FIRST chunk's variant: the carry omits
    the caches and the zeros are built inside this trace — under a serving
    mesh the first decode step's ``_constrain_cache`` then pins their
    sharding (heads over 'model') within the same program, where a separate
    zero-init jit would hand multi-GB replicated buffers across the jit
    boundary.  Subsequent chunks use the plain donated step.
    """
    def step(variables, ipb, tb, end_iterations, q_hi, fargs, carry):
        if init_caches:
            q, token_x, *rest = carry
            caches = {k: jnp.zeros(v.shape, v.dtype) for k, v in
                      decode_cache_shapes(model, variables,
                                          token_x).items()}
            carry = (q, token_x, caches, *rest)
        end_iterations = jnp.minimum(end_iterations, carry[1].shape[1])
        body_fn = _kv_body(model, mesh, logits_filter, variables, ipb, tb,
                           fargs if logits_filter else None)

        def cond_fn(state):
            return (state[0] < end_iterations - 1) & (state[0] < q_hi)

        return jax.lax.while_loop(cond_fn, body_fn, carry)

    return step


def decode_cache_bytes(model: Model, variables, token_x) -> int:
    """Total bytes of the decode-cache pytree (abstract — no allocation);
    drives the ``decode_loop: "auto"`` fused-vs-stepped routing."""
    cache = model.__dict__.setdefault("_decode_cache_bytes", {})
    # the cache dtype is part of the key: params mutated on a live model
    # (the int8 A/B pattern) must not serve a stale byte count
    key = (tuple(token_x.shape), str(model.params.decode_cache_dtype))
    if key not in cache:
        shapes = decode_cache_shapes(model, variables, token_x)
        cache[key] = sum(int(np.prod(v.shape)) * jnp.dtype(v.dtype).itemsize
                         for v in shapes.values())
    return cache[key]


def _use_stepped_loop(model: Model, variables, token_x) -> bool:
    p = model.params
    mode = getattr(p, "decode_loop", "auto")
    if mode == "fused":
        return False
    if mode == "stepped":
        return True
    threshold = float(p.decode_stepped_min_cache_gb) * 1024 ** 3
    return decode_cache_bytes(model, variables, token_x) >= threshold


def _sample_kv_stepped(model: Model, variables, token_x, initial_pos,
                       temperature, end_iterations, key, mesh=None,
                       prefill: bool = False, fargs=()):
    """Host-side driver for the stepped decode loop: prefill (or zero-init)
    the caches in their own jitted call, then walk the token loop as
    ``ceil(steps / decode_chunk_tokens)`` dispatches of the DONATED chunk
    step.  Per-dispatch latency amortises over the chunk; the donated carry
    keeps one live copy of the caches across the whole generation."""
    p = model.params
    filt = bool(fargs)
    batch, seq = token_x.shape[0], token_x.shape[1]
    ipb_host = np.broadcast_to(np.asarray(initial_pos, np.int32), (batch,))
    ipb = jnp.asarray(ipb_host)
    tb = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (batch,))
    if filt:
        top_k, top_p, rep = fargs
        fargs = (jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (batch,)),
                 jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (batch,)),
                 jnp.broadcast_to(jnp.asarray(rep, jnp.float32), (batch,)))
    end = int(min(int(np.asarray(end_iterations)), seq))
    suffix = "+filter" if filt else ""

    token_x, seen0 = _jit_sampler(model, mesh, "kv_prep" + suffix)(
        token_x, ipb)
    step = _jit_sampler(model, mesh, "kv_step" + suffix)
    chunk = max(1, int(getattr(p, "decode_chunk_tokens", 64)))
    end_dev = jnp.asarray(end, jnp.int32)

    # decode-progress instrumentation: with no hook installed (the default
    # outside serving) this adds NOTHING to the loop — no clock reads and
    # no per-chunk sync; with one, each chunk pays a block on the scalar q
    # (forces the chunk to completion; trivial next to chunk decode time)
    hook = decode_progress_hook()
    # per-ROW first-token thresholds: co-batched prompts of different
    # lengths reach their first generated token at different chunks, and
    # TTFT must close per request — a single batch-wide event would record
    # the longest prompt's TTFT as if it finished with the shortest
    ipb_row = np.maximum(1, ipb_host.astype(np.int64))
    first_fired = np.zeros(batch, bool)
    # cache bytes read EAGERLY: later chunks donate token_x away, and
    # decode_cache_bytes (shape-only, cached per model) must not touch a
    # deleted array
    cache_bytes = decode_cache_bytes(model, variables, token_x) \
        if hook is not None else 0

    def safe_hook(event: str, **kw):
        # telemetry must never fail a decode — but say so
        try:
            hook(event, **kw)
        except Exception as exc:
            import warnings
            warnings.warn(f"decode-progress hook failed: {exc!r}")

    def run_chunk(call, q_old: int, q_new: int):
        if hook is None:
            return call()
        t0 = time.monotonic()
        out = call()
        jax.block_until_ready(out[0])
        dt = time.monotonic() - t0
        safe_hook("chunk", dt=dt, steps=max(0, q_new - q_old),
                  cache_bytes=cache_bytes)
        newly = np.nonzero(~first_fired & (ipb_row <= q_new))[0]
        if newly.size:
            first_fired[newly] = True
            safe_hook("first_token", rows=newly.tolist())
        return out

    def flush_first_tokens():
        # a decode can END with rows that never crossed their first-token
        # threshold: a zero-chunk early return (end_iterations at/below the
        # chunk floor) or a prompt longer than the decode budget.  Close
        # them at completion so every stepped request contributes exactly
        # one TTFT sample — dropping them would exclude precisely the
        # cheapest traffic and bias the quantiles upward
        if hook is None:
            return
        rows = np.nonzero(~first_fired)[0]
        if rows.size:
            first_fired[rows] = True
            safe_hook("first_token", rows=rows.tolist())

    if prefill:
        # one full forward captures the caches decode steps 0..n0-1 would
        # write (make_kv_sampler documents the q/ipb arithmetic); runs on
        # the PREPPED token_x so the captured rows match the fused path.
        # Dispatched async — its time lands in the first steady chunk's dt
        q0 = max(int(ipb_host.min()) - 1, 0)
        caches = _jit_sampler(model, mesh, "kv_prefill_caches")(
            variables, token_x, jnp.asarray(q0, jnp.int32))
        carry = (jnp.asarray(q0, jnp.int32), token_x, caches, key)
        if filt:
            carry = carry + (seen0,)
        q = q0
    else:
        # the first chunk builds the zero caches INSIDE its own trace (the
        # "kv_step_init" kind) so a serving mesh constrains their sharding
        # in-program; it returns the full carry for the donated steady loop
        q0, q = 0, min(chunk, end - 1)
        if q <= 0:
            flush_first_tokens()
            return token_x  # nothing to generate
        carry0 = (jnp.asarray(q0, jnp.int32), token_x, key)
        if filt:
            carry0 = carry0 + (seen0,)
        carry = run_chunk(
            lambda: _jit_sampler(model, mesh, "kv_step_init" + suffix)(
                variables, ipb, tb, end_dev, jnp.asarray(q, jnp.int32),
                fargs, carry0), q0, q)
    while q < end - 1:
        q_hi = min(q + chunk, end - 1)
        carry = run_chunk(
            lambda c=carry, qh=q_hi: step(variables, ipb, tb, end_dev,
                                          jnp.asarray(qh, jnp.int32), fargs,
                                          c), q, q_hi)
        q = q_hi
    flush_first_tokens()
    return carry[1]


def _jit_sampler(model: Model, mesh, kind: str):
    """Per-model cache of the jitted samplers: ``jax.jit`` keyed on function
    identity would otherwise re-trace on EVERY ``sample_text`` call (each
    call built a fresh closure) — for serving that was a re-trace per
    request."""
    cache = model.__dict__.setdefault("_sampler_jit_cache", {})
    key = (mesh, kind)
    if key not in cache:
        # "+filter" kinds compile the top-k/top-p mask into the loop body;
        # the plain kinds keep the exact unfiltered program (identical XLA
        # to before the feature existed)
        filt = kind.endswith("+filter")
        base = kind[:-len("+filter")] if filt else kind
        if base == "kv":
            fn = jax.jit(make_kv_sampler(model, mesh=mesh, logits_filter=filt))
        elif base == "kv_prefill":
            fn = jax.jit(make_kv_sampler(model, mesh=mesh, prefill=True,
                                         logits_filter=filt))
        elif base == "kv_step":
            # the stepped path's chunk: carry (argument 6) DONATED so XLA
            # aliases every cache buffer input->output — the in-place
            # property analysis/hlo_lint.py asserts on the compiled module
            fn = jax.jit(make_kv_step(model, mesh=mesh, logits_filter=filt),
                         donate_argnums=(6,))
        elif base == "kv_step_init":
            # first chunk: zero caches built in-trace (mesh-constrained by
            # the first decode step); cacheless carry still donated
            fn = jax.jit(make_kv_step(model, mesh=mesh, logits_filter=filt,
                                      init_caches=True),
                         donate_argnums=(6,))
        elif base == "kv_prep":
            fn = jax.jit(lambda t, ipb: _kv_prep(model, t, ipb, filt))
        elif base == "kv_prefill_caches":
            def _prefill_caches(variables, token_x, n0):
                produced = model.apply_prefill(variables, token_x, n0,
                                               mesh=mesh)
                expected = decode_cache_shapes(model, variables, token_x)
                return _match_cache_layout(model, produced, expected)
            fn = jax.jit(_prefill_caches)
        else:
            fn = jax.jit(make_sampler(model, mesh=mesh, logits_filter=filt))
        cache[key] = fn
    return cache[key]


def sample_text(model: Model, variables, prompt_tokens, initial_pos=None,
                temperature=None, end_iterations=None, seed: int = 0,
                use_cache: bool = True, pad_random: bool = False, mesh=None,
                top_k=None, top_p=None, repetition_penalty=None):
    """Convenience host-level entry (pads/crops the prompt to sequence
    length); prompt_tokens: int array [batch, <=seq] or [batch, seq, patch].

    ``pad_random`` fills the region beyond the prompt with uniform random
    tokens instead of zeros (reference interface.py:263); with causal
    attention the generated stream is identical either way — it is parity
    surface for the interactive modes.

    ``mesh``: serving mesh — variables are expected to already carry their
    NamedShardings (run/modes.py ``_load_model``); the prompt is placed
    batch-over-'data' when divisible, and the decode KV caches inherit the
    attention activation layout (heads over 'model') via the constraint in
    model/decode.py ``spread``."""
    import numpy as np
    params = model.params
    seq = params.sequence_length // params.token_patch_size
    tps = params.token_patch_size
    prompt = np.asarray(prompt_tokens)
    if prompt.ndim == 2:
        prompt = prompt[:, :, None]
    batch = prompt.shape[0]
    if pad_random:
        token_x = np.random.default_rng(seed).integers(
            0, params.vocab_size, (batch, seq, tps)).astype(np.int32)
    else:
        token_x = np.zeros((batch, seq, tps), np.int32)
    n = min(seq, prompt.shape[1])
    token_x[:, :n] = prompt[:, :n]
    if initial_pos is None:
        initial_pos = min(params.initial_autoregressive_position, n)
    if temperature is None:
        temperature = params.sampling_temperature
    if end_iterations is None:
        end_iterations = seq
    if top_k is None:
        top_k = params.sampling_top_k
    if top_p is None:
        top_p = params.sampling_top_p
    if repetition_penalty is None:
        repetition_penalty = params.sampling_repetition_penalty
    # static routing: the filter kinds compile the top-k/top-p/repetition
    # machinery in; the default path's XLA program stays byte-identical to
    # pre-feature
    filt = (np.max(np.asarray(top_k)) > 0
            or np.min(np.asarray(top_p)) < 1.0
            or bool(np.any(np.asarray(repetition_penalty) != 1.0)))
    fargs = ((jnp.asarray(top_k, jnp.int32),
              jnp.asarray(top_p, jnp.float32),
              jnp.asarray(repetition_penalty, jnp.float32)) if filt else ())
    tokens_in = jnp.asarray(token_x)
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from ..core import sharding as shardlib
        data = mesh.shape.get(shardlib.DATA_AXIS, 1)
        spec = (PartitionSpec(shardlib.DATA_AXIS)
                if batch % data == 0 and data > 1
                else PartitionSpec())
        tokens_in = jax.device_put(tokens_in, NamedSharding(mesh, spec))
    if use_cache and not params.use_video:
        try:
            # prompts beyond position 1 prefill in one full forward instead
            # of walking the prompt one decode step per token (O(1) model
            # calls to first generated token); initial_pos <= 1 has nothing
            # to prefill
            prefill = int(np.min(initial_pos)) > 1
            if _use_stepped_loop(model, variables, tokens_in):
                # big caches: host loop over donated chunk steps — the
                # cache carry aliases in place (decode_loop config knob;
                # docs/PERFORMANCE.md 'Big-cache decode')
                out = _sample_kv_stepped(
                    model, variables, tokens_in,
                    jnp.asarray(initial_pos, jnp.int32),
                    jnp.asarray(temperature, jnp.float32),
                    int(np.asarray(end_iterations)),
                    jax.random.PRNGKey(seed), mesh=mesh, prefill=prefill,
                    fargs=fargs)
                return np.asarray(out)
            kind = "kv_prefill" if prefill else "kv"
            fn = _jit_sampler(model, mesh, kind + "+filter" if filt else kind)
            out = fn(variables, tokens_in,
                     jnp.asarray(initial_pos, jnp.int32),
                     jnp.asarray(temperature, jnp.float32),
                     jnp.asarray(end_iterations, jnp.int32),
                     jax.random.PRNGKey(seed), None, *fargs)
            return np.asarray(out)
        except NotImplementedError:
            pass  # layer without a streaming form: full-forward fallback
    fn = _jit_sampler(model, mesh, "full+filter" if filt else "full")
    out = fn(variables, tokens_in, tokens_in,
             jnp.asarray(initial_pos, jnp.int32),
             jnp.asarray(temperature, jnp.float32),
             jnp.asarray(end_iterations, jnp.int32),
             jax.random.PRNGKey(seed), *fargs)
    return np.asarray(out)


def sample_video(model: Model, variables, batch, initial_pos=None,
                 steps: typing.Optional[int] = None):
    """Autoregressive video continuation (reference inference.py:25-73).

    Host-side frame loop: each step runs the full forward, writes the
    predicted next frame (sigmoid output, rescaled to input units) into the
    frame input at the current position, and — in language mode — the argmax
    tokens into ``token_x`` at that position.  Returns (frames01, tokens):
    frames01 float [batch, seq+1, ...] in [0, 1], tokens int or None.
    """
    import numpy as np
    params = model.params
    if initial_pos is None:
        initial_pos = params.initial_autoregressive_position
    seq = params.time_patch_size
    end = seq if steps is None else min(seq, initial_pos + steps)

    def _fwd(v, b):
        info = model.apply(v, b)
        return (info.frame_out.data,
                info.token_out.data if params.use_language else jnp.zeros(()))

    fwd = jax.jit(_fwd)

    batch = dict(batch)
    frame = np.asarray(batch["frame"]).astype(np.float32)
    token_x = (np.asarray(batch["token_x"]) if params.use_language else None)
    for pos in range(max(1, initial_pos), end):
        out_frame, out_token = fwd(variables, {**batch,
                                               "frame": jnp.asarray(frame),
                                               **({"token_x": jnp.asarray(token_x)}
                                                  if token_x is not None else {})})
        # frame_out[:, t] / token_out[:, t] predict position t+1 (src/tgt
        # shift: data tgt = frames[1:], token_y = tokens[1:]).  The reference
        # writes its prediction at the unshifted position
        # (/root/reference/src/run/inference.py body_fn, near its own
        # "todo: fix token shift") — the shift here deliberately corrects
        # that off-by-one rather than reproducing it.
        pred = np.asarray(out_frame)[:, pos - 1]
        frame[:, pos] = pred * 255.0
        if token_x is not None:
            tok = np.argmax(np.asarray(out_token), axis=-1)       # [b, s, ...]
            token_x = token_x.copy()
            token_x[:, pos] = tok[:, pos - 1].reshape(token_x[:, pos].shape)
    return frame / 255.0, token_x
