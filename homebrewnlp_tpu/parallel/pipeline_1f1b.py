"""Fused 1F1B pipeline schedule (opt-in: ``pipeline_schedule = "1f1b"``).

GPipe (parallel/pipeline.py, the default) runs all M microbatch forwards,
then autodiff generates the full backward — every stage stashes M microbatch
residuals and the backward cannot start until the last forward finishes.
1F1B interleaves them: each stage runs ``min(M, S - s)`` warmup forwards and
then strictly alternates backward/forward, so at most ``S - s`` microbatches
are ever in flight per stage (activation stash O(S) instead of O(M)) and the
backward of microbatch 0 starts S ticks after its forward instead of M.

That fusion is only possible with the output head + loss INSIDE the last
stage (the backward of microbatch m needs its loss cotangent before the
other microbatches have even run forward), so this module computes loss AND
gradients in one forward-only pass: per-stage ``jax.vjp`` re-traces the
existing strategy machinery (rev/momentum custom-vjp sequences, checkpoint)
for the backward units, parameter gradients accumulate in the scan carry,
and the schedule is a static per-tick table.  The reference has no pipeline
parallelism at all (SURVEY.md §2.10); GPipe stays the default because its
autodiff backward avoids 1F1B's per-unit forward recompute — choose 1f1b
when activation memory or time-to-first-backward dominates.

Text (gpt) models only; the multi-loss strategies (pcgrad/mgda) and
contrastive losses keep the GPipe path.
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core.dims import Dim
from ..core.tensor import NamedTensor, nt
from .pipeline import AXIS, _stack_stages, _stage_layout
from jax import shard_map

# kinds, mbs, chunks: [ticks, S] int32 tables
Schedule = typing.Tuple[np.ndarray, np.ndarray, np.ndarray]

IDLE, FWD, BWD = 0, 1, 2


def _unit_order(n_micro: int, n_stages: int, n_chunks: int, stage: int
                ) -> typing.List[typing.Tuple[str, int, int]]:
    """Per-device unit firing ORDER (kind, microbatch, chunk).

    ``n_chunks == 1``: the classic non-interleaved 1F1B order (min(M, S-s)
    warmup forwards, strict B/F alternation, trailing backwards).

    ``n_chunks > 1``: the interleaved virtual-stage order (Megatron-LM PP
    interleaving): device s owns chunks ``c*S + s``; forward unit j maps to
    chunk ``(j mod S·V) div S`` and microbatch ``(j div S·V)·S + j mod S``
    (microbatch groups of S cycle through the chunks), the backward sequence
    mirrors it with chunks reversed, and the warmup is
    ``(S - s - 1)·2 + (V - 1)·S`` units — shrinking the bubble by ~1/V at
    the price of V× more ring hops."""
    M, S, V = n_micro, n_stages, n_chunks
    if V == 1:
        warm = min(M, S - stage)
        units = [("F", m, 0) for m in range(warm)]
        for m in range(M - warm):
            units.append(("B", m, 0))
            units.append(("F", warm + m, 0))
        units.extend(("B", m, 0) for m in range(M - warm, M))
        return units
    if M % S:
        raise ValueError(f"interleaved 1F1B needs microbatches ({M}) "
                         f"divisible by stages ({S})")

    def fwd_unit(j):
        return ("F", (j // (S * V)) * S + j % S, (j % (S * V)) // S)

    def bwd_unit(j):
        return ("B", (j // (S * V)) * S + j % S, V - 1 - (j % (S * V)) // S)

    total = M * V
    warm = min((S - stage - 1) * 2 + (V - 1) * S, total)
    units = [fwd_unit(j) for j in range(warm)]
    # steady state is F-then-B here (the first backward's own forward is the
    # first steady unit on the last stage), unlike the B-first non-
    # interleaved steady above whose warmup already covers it
    for j in range(total - warm):
        units.append(fwd_unit(warm + j))
        units.append(bwd_unit(j))
    units.extend(bwd_unit(j) for j in range(total - warm, total))
    return units


def build_schedule(n_micro: int, n_stages: int, n_chunks: int = 1) -> Schedule:
    """Static 1F1B tick table (optionally interleaved over virtual chunks).

    Each device fires its units in ``_unit_order`` at the earliest tick the
    dataflow allows: F(m,c,s) needs F(m,c,s-1) — or F(m,c-1,S-1) ring-wrapped
    when s==0, c>0; B(m,c,s) needs its own F plus B(m,c,s+1) — or
    B(m,c+1,0) wrapped when s==S-1, c<V-1 (the loss head seeds B(m,V-1,S-1)).
    """
    M, S, V = n_micro, n_stages, n_chunks
    seq = [_unit_order(M, S, V, s) for s in range(S)]

    fwd_done = np.full((M, V, S), -1, np.int64)  # tick the unit completed
    bwd_done = np.full((M, V, S), -1, np.int64)
    pos = [0] * S
    kinds, mbs, chunks = [], [], []
    t = 0
    while any(pos[s] < len(seq[s]) for s in range(S)):
        krow, mrow, crow = [IDLE] * S, [0] * S, [0] * S
        fired = False
        for s in range(S):
            if pos[s] >= len(seq[s]):
                continue
            kind, m, c = seq[s][pos[s]]

            def done(tbl, mm, cc, ss):
                return tbl[mm, cc, ss] >= 0 and tbl[mm, cc, ss] < t
            if kind == "F":
                if s > 0:
                    ready = done(fwd_done, m, c, s - 1)
                else:
                    ready = c == 0 or done(fwd_done, m, c - 1, S - 1)
            else:
                ready = done(fwd_done, m, c, s)
                if s < S - 1:
                    ready = ready and done(bwd_done, m, c, s + 1)
                elif c < V - 1:
                    ready = ready and done(bwd_done, m, c + 1, 0)
            if ready:
                krow[s] = FWD if kind == "F" else BWD
                mrow[s] = m
                crow[s] = c
                (fwd_done if kind == "F" else bwd_done)[m, c, s] = t
                pos[s] += 1
                fired = True
        assert fired, "schedule deadlock"
        kinds.append(krow)
        mbs.append(mrow)
        chunks.append(crow)
        t += 1
    return (np.asarray(kinds, np.int32), np.asarray(mbs, np.int32),
            np.asarray(chunks, np.int32))


def bubble_ticks(kinds: np.ndarray) -> int:
    """Idle (stage, tick) cells across the schedule — the pipeline bubble."""
    return int((kinds == IDLE).sum())


def _choose_slots(kinds: np.ndarray, mbs: np.ndarray, chunks: np.ndarray,
                  n_stages: int, n_chunks: int) -> int:
    """Smallest stash size P such that ``m mod P`` is collision-free among
    the microbatches LIVE (activation arrived, backward pending) per
    (stage, chunk).  Liveness runs from the ring ARRIVAL of the forward
    activation (one tick after the upstream forward fired; own tick for
    stage 0 chunk 0, which reads the raw input) to the tick of the own
    backward.  Non-interleaved 1F1B provably fits ``S + 1``; the interleaved
    warmup can hold more, so verify statically instead of hoping."""
    ticks = kinds.shape[0]
    S, V, M = n_stages, n_chunks, int(mbs.max()) + 1
    fwd_tick = np.full((M, V, S), -1, np.int64)
    bwd_tick = np.full((M, V, S), -1, np.int64)
    for t in range(ticks):
        for s in range(S):
            m, c = int(mbs[t, s]), int(chunks[t, s])
            if kinds[t, s] == FWD:
                fwd_tick[m, c, s] = t
            elif kinds[t, s] == BWD:
                bwd_tick[m, c, s] = t
    # forward-activation liveness windows [arrival, backward] per
    # (stage, chunk) — the ``stash`` buffer
    windows: dict = {}
    # backward-cotangent windows for the sibling ``bstash`` buffer, which
    # reuses the same ``m mod P`` slot modulus: the cotangent for B(m,c,s)
    # arrives one tick after the downstream backward fired (B(m,c,s+1), or
    # ring-wrapped B(m,c+1,0) when s==S-1) and is consumed at the own B
    # tick.  The last stage's last chunk seeds its cotangent locally from
    # the loss head — no slot, no window.
    bwindows: dict = {}
    for m in range(M):
        for c in range(V):
            for s in range(S):
                if fwd_tick[m, c, s] < 0:
                    continue
                if s > 0:
                    arrive = fwd_tick[m, c, s - 1] + 1
                elif c > 0:
                    arrive = fwd_tick[m, c - 1, S - 1] + 1
                else:
                    arrive = fwd_tick[m, c, s]
                windows.setdefault((s, c), []).append(
                    (m, arrive, bwd_tick[m, c, s]))
                if s < S - 1:
                    b_arrive = bwd_tick[m, c, s + 1] + 1
                elif c < V - 1:
                    b_arrive = bwd_tick[m, c + 1, 0] + 1
                else:
                    continue  # loss-head seed, never stashed
                bwindows.setdefault((s, c), []).append(
                    (m, b_arrive, bwd_tick[m, c, s]))

    def collision_free(win_map, p):
        for wins in win_map.values():
            for i, (m1, a1, b1) in enumerate(wins):
                for m2, a2, b2 in wins[i + 1:]:
                    if m1 % p == m2 % p and a1 <= b2 and a2 <= b1:
                        return False
        return True

    for p in range(S + 1, S * V + V + 3):
        if collision_free(windows, p) and collision_free(bwindows, p):
            return p
    raise AssertionError("no collision-free stash size found")


def pipeline_train_1f1b(params, mesh: Mesh, fns, subsets, plan,
                        src: NamedTensor, tgt_mb: jax.Array,
                        head_fn: typing.Callable,
                        head_params: typing.Dict[str, jax.Array],
                        n_aux: int, strategy: str):
    """Fused forward+backward over the 'pipe' axis.

    ``head_fn(head_params, y_combined, tgt) -> (loss, aux[n_aux])`` runs per
    microbatch on the last stage.  Returns (mean loss, mean aux vector,
    stage-stacked body grads ([S, ...] leaves, same tree as the stacked
    params), head-param grads, d_src — the loss cotangent of ``src``).
    """
    from ..model.blocks import momentum_sequence, rev_sequence
    from ..core import scope

    n_stages = mesh.shape[AXIS]
    n_virtual = max(1, int(getattr(params, "pipeline_interleave", 1) or 1))
    n_micro = max(1, int(params.pipeline_microbatches or n_stages))
    batch = src.dims[0]
    if batch.size % n_micro:
        raise ValueError(f"batch {batch.size} not divisible by "
                         f"pipeline_microbatches={n_micro}")
    mb = batch.size // n_micro
    if mb % mesh.shape.get("data", 1):
        raise ValueError(f"microbatch {mb} not divisible by data parallelism")

    # chunk g = c * S + s lives on device s as its c-th virtual chunk
    # (Megatron-style round-robin), so the ring hop s -> s+1 stays
    # chunk-preserving and the wrap S-1 -> 0 advances the chunk
    stage0_fns, name_lists, chunk_leaves = _stage_layout(
        fns, subsets, plan, n_stages * n_virtual)
    stacked = jax.tree.map(
        lambda *xs: jnp.stack(xs),
        *[_stack_stages([chunk_leaves[c * n_stages + s]
                         for s in range(n_stages)])
          for c in range(n_virtual)])              # leaves [V, S, ...]
    kinds_np, mbs_np, chunks_np = build_schedule(n_micro, n_stages, n_virtual)
    ticks = kinds_np.shape[0]
    stash_slots = _choose_slots(kinds_np, mbs_np, chunks_np, n_stages,
                                n_virtual)
    # a unit may fire LATER than one tick after its payload arrives (stages
    # interleave B units), so receives are filed into per-(chunk, microbatch)
    # slot buffers via static store tables instead of being consumed off the
    # ring directly: f_store[t, s] = flattened (chunk, slot) index to store
    # this tick's incoming forward activation, -1 = nothing arriving.  The
    # wrap hops (only live when interleaving) file into the NEXT chunk
    # forward / the PREVIOUS chunk backward.
    f_store_np = np.full((ticks, n_stages), -1, np.int32)
    b_store_np = np.full((ticks, n_stages), -1, np.int32)
    for t in range(1, ticks):
        for s in range(n_stages):
            prev = s - 1 if s > 0 else (n_stages - 1 if n_virtual > 1 else None)
            if prev is not None and kinds_np[t - 1, prev] == FWD:
                cs = chunks_np[t - 1, prev] + (0 if s > 0 else 1)
                if cs < n_virtual:
                    f_store_np[t, s] = (cs * stash_slots
                                        + mbs_np[t - 1, prev] % stash_slots)
            nxt = s + 1 if s < n_stages - 1 else (0 if n_virtual > 1 else None)
            if nxt is not None and kinds_np[t - 1, nxt] == BWD:
                cs = chunks_np[t - 1, nxt] - (0 if s < n_stages - 1 else 1)
                if cs >= 0:
                    b_store_np[t, s] = (cs * stash_slots
                                        + mbs_np[t - 1, nxt] % stash_slots)
    kinds = jnp.asarray(kinds_np)
    mbs = jnp.asarray(mbs_np)
    chunk_rows = jnp.asarray(chunks_np)
    f_store = jnp.asarray(f_store_np)
    b_store = jnp.asarray(b_store_np)

    n_stream = 2 if strategy in ("revnet", "momentum") else 1
    mb_dims = (Dim(batch.name, mb),) + tuple(src.dims[1:])
    xm = src.data.reshape((n_micro, mb) + src.data.shape[1:])

    def stage_apply(flat_params, state):
        subs = [dict(zip(names, arrs))
                for names, arrs in zip(name_lists, flat_params)]
        if strategy == "revnet":
            y1, y2 = rev_sequence(stage0_fns, tuple(subs),
                                  nt(state[0], mb_dims), nt(state[1], mb_dims))
            return jnp.stack([y1.data, y2.data])
        if strategy == "momentum":
            y, v = momentum_sequence(stage0_fns, params.momentumnet_alpha,
                                     tuple(subs),
                                     nt(state[0], mb_dims), nt(state[1], mb_dims))
            return jnp.stack([y.data, v.data])
        out = nt(state[0], mb_dims)
        for f, sub in zip(stage0_fns, subs):
            out = jax.checkpoint(f)(sub, out) if strategy == "checkpoint" \
                else f(sub, out)
        return out.data[None]

    def combine(state):
        return state[0] + state[1] if n_stream == 2 else state[0]

    ctx = scope.current() if scope.in_context() else None
    base_rng = ctx.rng_key if ctx is not None else None

    def body(stacked_local, head_p, xm_local, tgt_local):
        stage = jax.lax.axis_index(AXIS)
        # leaves arrive [V, 1, ...] (chunk axis unsharded, stage axis local)
        local = jax.tree.map(lambda a: jnp.squeeze(a, 1), stacked_local)
        is_last = stage == n_stages - 1

        def chunk_params(c):
            return jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(a, c, 0,
                                                       keepdims=False), local)

        def with_rng(m, c, fn, *args):
            if ctx is None or base_rng is None:
                return fn(*args)
            # reset BOTH the folded key and the draw counter: the backward
            # unit's vjp re-trace must consume identical next_rng() draws as
            # the forward unit that produced the activation (the counter is
            # Python trace state and would otherwise keep counting across
            # units, giving the recompute different dropout masks).  The key
            # folds the GLOBAL chunk index (== stage when not interleaved).
            saved_count = ctx._rng_count
            ctx.rng_key = jax.random.fold_in(
                jax.random.fold_in(base_rng, c * n_stages + stage), m)
            ctx._rng_count = 0
            try:
                return fn(*args)
            finally:
                ctx.rng_key = base_rng
                ctx._rng_count = saved_count

        state_shape = (n_stream, mb) + xm_local.shape[2:]
        dtype = xm_local.dtype
        n_slots_total = n_virtual * stash_slots

        def tick(carry, sched_row):
            (f_recv, b_recv, stash, bstash, grads, hgrads, loss_acc, aux_acc,
             d_src_acc) = carry
            krow, mrow, crow, frow, brow = sched_row
            code = jnp.take(krow, stage)
            m = jnp.take(mrow, stage)
            c = jnp.take(crow, stage)
            slot = c * stash_slots + jnp.mod(m, stash_slots)
            params_c = chunk_params(c)

            # file this tick's ring arrivals into their (chunk, mb) slots
            fslot = jnp.take(frow, stage)
            stash = jax.lax.cond(
                fslot >= 0,
                lambda: jax.lax.dynamic_update_index_in_dim(
                    stash, f_recv, jnp.maximum(fslot, 0), 0),
                lambda: stash)
            bslot = jnp.take(brow, stage)
            bstash = jax.lax.cond(
                bslot >= 0,
                lambda: jax.lax.dynamic_update_index_in_dim(
                    bstash, b_recv, jnp.maximum(bslot, 0), 0),
                lambda: bstash)

            x0 = jax.lax.dynamic_index_in_dim(
                xm_local, jnp.minimum(m, n_micro - 1), 0, keepdims=False)
            state0 = jnp.broadcast_to(x0[None], state_shape).astype(dtype)
            stashed = jax.lax.dynamic_index_in_dim(stash, slot, 0,
                                                   keepdims=False)
            # only the pipeline entry (stage 0, chunk 0) reads the raw input;
            # later chunks on stage 0 read the wrap arrival from the stash
            x_in = jnp.where((stage == 0) & (c == 0), state0, stashed)

            def zero_like_grads():
                return (jax.tree.map(jnp.zeros_like, grads),
                        jax.tree.map(jnp.zeros_like, hgrads))

            def fwd_unit(_):
                y = with_rng(m, c, stage_apply, params_c, x_in)
                new_stash = jax.lax.dynamic_update_index_in_dim(
                    stash, x_in, slot, 0)
                zg, zh = zero_like_grads()
                return (y, new_stash, zg, zh, jnp.float32(0),
                        jnp.zeros((n_aux,), jnp.float32),
                        jnp.zeros_like(x0), jnp.zeros(state_shape, dtype),
                        jnp.int32(0))

            def bwd_unit(_):
                xs = jax.lax.dynamic_index_in_dim(stash, slot, 0,
                                                  keepdims=False)
                tgt = jax.lax.dynamic_index_in_dim(
                    tgt_local, jnp.minimum(m, n_micro - 1), 0, keepdims=False)

                def last_loss(p_, x_, h_):
                    # the stage's forward ran in its forward unit; the head
                    # runs here for the first time
                    with jax.named_scope(scope.REPLAY):
                        y_ = stage_apply(p_, x_)
                    loss, aux = head_fn(h_, combine(y_), tgt)
                    return loss, aux

                def run_last():
                    loss, vjp, aux = with_rng(
                        m, c, lambda: jax.vjp(last_loss, params_c, xs, head_p,
                                              has_aux=True))
                    # the overall loss is the MEAN over microbatches: seed
                    # each microbatch's backward with 1/M
                    dparams, dx, dh = vjp(jnp.asarray(1.0 / n_micro,
                                                      loss.dtype))
                    dh = jax.tree.map(lambda a: a.astype(jnp.float32), dh)
                    return (dparams, dh, dx, loss.astype(jnp.float32),
                            aux.astype(jnp.float32))

                def run_mid():
                    cot = jax.lax.dynamic_index_in_dim(bstash, slot, 0,
                                                       keepdims=False)
                    _, vjp = with_rng(
                        m, c, lambda: scope.replay_vjp(stage_apply, params_c,
                                                       xs))
                    dparams, dx = vjp(cot)
                    return (dparams, jax.tree.map(jnp.zeros_like, hgrads),
                            dx, jnp.float32(0),
                            jnp.zeros((n_aux,), jnp.float32))

                # the loss head hangs off the LAST chunk of the last stage
                dparams, dh, dx, loss, aux = jax.lax.cond(
                    is_last & (c == n_virtual - 1), run_last, run_mid)
                # scatter this chunk's param grads into the [V, ...] slot
                dg = jax.tree.map(
                    lambda z, d: jax.lax.dynamic_update_index_in_dim(
                        z, d, c, 0),
                    jax.tree.map(jnp.zeros_like, grads), dparams)
                d_src = jnp.where((stage == 0) & (c == 0), dx.sum(0),
                                  jnp.zeros_like(x0))
                return (jnp.zeros(state_shape, dtype), stash, dg, dh,
                        loss, aux, d_src, dx, jnp.int32(1))

            def idle_unit(_):
                zg, zh = zero_like_grads()
                return (jnp.zeros(state_shape, dtype), stash, zg, zh,
                        jnp.float32(0), jnp.zeros((n_aux,), jnp.float32),
                        jnp.zeros_like(x0), jnp.zeros(state_shape, dtype),
                        jnp.int32(0))

            (send_f, stash, dg, dh, dloss, daux, d_src, send_b, wrote) = \
                jax.lax.switch(code, [idle_unit, fwd_unit, bwd_unit],
                               operand=None)
            grads = jax.tree.map(jnp.add, grads, dg)
            hgrads = jax.tree.map(jnp.add, hgrads, dh)
            loss_acc = loss_acc + dloss
            aux_acc = aux_acc + daux
            prev = jax.lax.dynamic_index_in_dim(
                d_src_acc, jnp.minimum(m, n_micro - 1), 0, keepdims=False)
            # stage 0 fires B(m, c) for every chunk; only c == 0 carries the
            # real input cotangent and it fires LAST for its microbatch
            # (chunks unwind V-1 .. 0), so chunk>0 zero-writes land first
            d_src_acc = jax.lax.dynamic_update_index_in_dim(
                d_src_acc, jnp.where(wrote > 0, d_src, prev),
                jnp.minimum(m, n_micro - 1), 0)
            f_recv = jax.lax.ppermute(send_f, AXIS, fwd_links)
            b_recv = jax.lax.ppermute(send_b, AXIS, bwd_links)
            return (f_recv, b_recv, stash, bstash, grads, hgrads, loss_acc,
                    aux_acc, d_src_acc), None

        carry0 = (
            jnp.zeros(state_shape, dtype),
            jnp.zeros(state_shape, dtype),
            jnp.zeros((n_slots_total,) + state_shape, dtype),
            jnp.zeros((n_slots_total,) + state_shape, dtype),
            jax.tree.map(jnp.zeros_like, local),
            jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), head_p),
            jnp.float32(0),
            jnp.zeros((n_aux,), jnp.float32),
            jnp.zeros((n_micro,) + xm_local.shape[1:], xm_local.dtype),
        )
        (_, _, _, _, grads, hgrads, loss_acc, aux_acc, d_src_acc), _ = \
            jax.lax.scan(tick, carry0,
                         (kinds, mbs, chunk_rows, f_store, b_store))
        # grads live on their own stage; restore the stage axis for the
        # out_spec.  head/loss/d_src live on single stages: psum over pipe
        # replicates them.
        grads = jax.tree.map(lambda a: a[:, None], grads)
        hgrads = jax.tree.map(lambda a: jax.lax.psum(a, AXIS), hgrads)
        loss_acc = jax.lax.psum(loss_acc, AXIS) / n_micro
        aux_acc = jax.lax.psum(aux_acc, AXIS) / n_micro
        d_src_acc = jax.lax.psum(d_src_acc, AXIS)
        return grads, hgrads, loss_acc, aux_acc, d_src_acc

    fwd_links = [(i, i + 1) for i in range(n_stages - 1)] \
        + ([(n_stages - 1, 0)] if n_virtual > 1 else [])
    bwd_links = [(i + 1, i) for i in range(n_stages - 1)] \
        + ([(0, n_stages - 1)] if n_virtual > 1 else [])
    param_specs = jax.tree.map(lambda _: P(None, AXIS), stacked)
    head_specs = jax.tree.map(lambda _: P(), head_params)
    fn = shard_map(
        body, mesh=mesh,
        in_specs=(param_specs, head_specs, P(), P()),
        out_specs=(param_specs, head_specs, P(), P(), P()),
        axis_names={AXIS}, check_vma=False)

    saved_mesh = ctx.mesh if ctx is not None else None
    if ctx is not None:
        ctx.mesh = None
    try:
        grads, hgrads, loss, aux, d_src = fn(stacked, head_params, xm, tgt_mb)
    finally:
        if ctx is not None:
            ctx.mesh = saved_mesh

    # chunk/stage-stacked grads -> flat names (shared weights sum across
    # blocks); global chunk c*S + s holds blocks (c*S + s)*per_chunk + k
    flat: typing.Dict[str, jax.Array] = {}
    per_chunk = len(fns) // (n_stages * n_virtual)
    for c in range(n_virtual):
        for s in range(n_stages):
            for k_local in range(per_chunk):
                k = (c * n_stages + s) * per_chunk + k_local
                names = tuple(plan[k][2])
                for name, g in zip(names, grads[k_local]):
                    gs = g[c, s]
                    flat[name] = flat.get(name, 0) + gs
    d_src_nt = nt(d_src.reshape(src.data.shape), src.dims)
    return loss, aux, flat, hgrads, d_src_nt
