"""Continuous-batching decode engine: a fixed-slot KV pool on device.

The batch-to-completion serving path (``infer/rest_api.py`` + ``sampler``)
assembles a batch, decodes EVERY row to its end, then answers — one long
request pins its whole co-batch, and KV memory is provisioned per batch at
worst-case length.  This module is the device half of iteration-level
scheduling on top of PR 2's stepped decode substrate:

* **slot pool** — one donated decode carry sized ``serve_slots`` wide holds
  per-slot rows of every cache leaf (int8-composable: the sibling scale
  caches ride the same pool).  Allocated once, in-trace, on the first
  dispatch; every subsequent chunk step donates it, so XLA's
  input_output_aliases pin all cache updates in place (the PR 2 property,
  audited on the compiled module as ``engine_chunk_step`` by graft-lint).
* **per-slot positions** — the chunk step carries an int32 position VECTOR:
  co-resident requests decode at independent positions (model/decode.py
  ``scatter_rows`` + the vector-pos branches in compare_range/_embed), so a
  newly admitted request walks its prompt region while residents keep
  generating — prefill interleaved with decode at iteration granularity.
* **admit between chunks** — admission rides the chunk step itself: the
  ``engine_admit`` variant splices new prompt rows into the donated
  ``token_x``, resets the admitted slots' positions and ``seen`` counts, and
  zeroes their cache rows (a per-leaf elementwise select — the
  non-idempotent recurrence caches, cumsum totals and conv windows, must not
  inherit the previous occupant's state; KV rows would self-heal through the
  per-row causal mask but are cleared uniformly).  Finished slots are simply
  parked (``end_pos = 0``): their rows stop advancing and anything the pool
  still holds for them is dead weight the next admission overwrites.
* **per-slot end detection** — a slot is finished when its position reaches
  its own ``end_pos - 1``; the host reads back positions + tokens after
  every chunk (one small D2H of ``token_x``, never the cache pool), answers
  finished rows immediately and recycles their slots.

Sampling semantics match the stepped loop's ``_kv_body`` walk bit-for-bit
for greedy requests (tests/continuous_batching_test.py pins token-for-token
parity); the logits-filter machinery is always compiled in — with filters at
their disabled defaults it is an exact identity on the argmax, so the one
program serves both.  Temperature>0 rows draw per-step gumbel noise from one
engine-wide stream (the per-token distribution is identical to the batch
path; the realized stream depends on co-residency, like any shared-rng
batched sampler).

Host-side scheduling (FIFO admission, deadlines, breaker interplay) lives in
``infer/scheduler.py`` — device-free, so the state machine tests run without
jax work.  ``infer/rest_api.py`` wires both into the serving device loop
(config ``serve_engine`` auto/batch/continuous).

**Speculative decoding** (:class:`SpecEngineExecutor`, config
``spec_decode``; docs/SERVING.md 'Speculative decoding'): decode is
cache-bytes-bound, so the remaining serving lever is fewer sequential
full-model steps per emitted token.  Each round is ONE donated chunk call
(kinds ``spec_init``/``spec_admit``/``spec_plain``) carrying BOTH cache
pools — target and quarter-width draft — that (1) splices the host's
accept/reject decision from the previous round (correction token +
repetition-penalty catch-up), (2) runs k+1 sequential DRAFT steps (the +1
fills the draft KV row at q+k so a fully-accepted round leaves no cache
gap), writing k greedy draft tokens into ``token_x`` past each slot's
position, then (3) runs ONE width-(k+1) full-model VERIFY step
(``model.apply_decode`` with a k+1-long token slice per slot — the
multi-position decode path in model/decode.py) that scores every drafted
position against the full KV pool in a single cache read.  The host then
takes the longest-accepted-prefix per slot under greedy — emitted tokens
are accepted drafts plus the verify's own token at the first mismatch (or
the bonus token after full acceptance), so output is bit-identical to the
plain engine and progress is >= 1 token/slot/round even at total
rejection.  Rejected positions need NO explicit KV rollback: decode writes
every row before attending it and rows only ever re-fill left-to-right, so
the next round's verify overwrites every rejected row in both pools before
anything reads it (the same self-heal the admit splice relies on); the
admit row-zeroing covers slot recycling for both pools.  Models with
sequence-RECURRENT caches (cumsum, conv windows) cannot self-heal and are
refused at construction (model/decode.py raises on width > 1).  Per-slot
acceptance feeds the ``hbnlp_spec_*`` /metrics series, and a sliding-window
acceptance collapse below ``spec_min_accept_rate`` permanently reverts the
executor to the plain chunk program (graceful degradation, loudly).
"""
from __future__ import annotations

import typing

import numpy as np

from ..config import ModelParameter
from ..model import Model


def _splice_admitted(token_x, seen, ipb, mask, new_rows, pools):
    """Shared admit splice of the plain AND speculative chunk programs —
    one definition, because the two must stay bit-identical for the
    spec-vs-plain parity contract: swap the admitted prompt rows into
    ``token_x``, reseed the admitted rows' repetition-penalty counts from
    their prompt region (the ``_kv_prep`` formula — ipb==0 rows count the
    parity-zeroed index 0), and evict the previous occupant from every
    cache pool with a per-leaf elementwise select (no full-pool copy — the
    HLO audits check).  Returns (token_x, seen, pools)."""
    import jax.numpy as jnp

    from ..model import blocks as blocks_mod

    batch, seq = token_x.shape[0], token_x.shape[1]
    rows3 = jnp.arange(batch)[:, None, None]
    token_x = jnp.where(mask[:, None, None], new_rows, token_x)
    pmask = (jnp.arange(seq)[None, :, None]
             < jnp.maximum(ipb, 1)[:, None, None]).astype(jnp.float32)
    seeded = jnp.zeros_like(seen).at[rows3, token_x].add(pmask)
    seen = jnp.where(mask[:, None], seeded, seen)
    out_pools = []
    for pool in pools:
        pool = dict(pool)
        for name in list(pool):
            leaf = pool[name]
            baxis = 1 if name.startswith(
                blocks_mod.STACKED_CACHE_PREFIX) else 0
            bshape = [1] * leaf.ndim
            bshape[baxis] = batch
            pool[name] = jnp.where(mask.reshape(bshape),
                                   jnp.zeros((), leaf.dtype), leaf)
        out_pools.append(pool)
    return token_x, seen, out_pools


def _sample_logits(logits, seen, tb, fargs, key):
    """Shared filtered-gumbel token draw of the plain body AND the spec
    verify (one formula keeps greedy spec-vs-plain parity by
    construction): repetition penalty over ``seen``, top-k/top-p filters
    (exact identity on the argmax at disabled defaults), gumbel noise
    scaled by temperature.  Returns (sampled tokens, next key)."""
    import jax
    import jax.numpy as jnp

    from .sampler import _filter_logits, _repetition_penalty

    kb, pb, rb = fargs
    logits = logits.astype(jnp.float32)          # [b, w, tp, v]
    logits = _repetition_penalty(logits, seen, rb)
    logits = _filter_logits(logits, tb, kb, pb)
    key, sub = jax.random.split(key)
    u = jax.random.uniform(sub, logits.shape, jnp.float32,
                           minval=1e-9, maxval=1.0)
    logits = logits + jnp.log(-jnp.log(u)) * (-tb[:, None, None, None])
    return jnp.argmax(logits, axis=-1), key


def _engine_loop(model: Model, mesh, variables, ipb, tb, end_pos, steps,
                 fargs, q, token_x, caches, key, seen):
    """The engine's decode while-loop: up to ``steps`` live iterations of
    (read token at q -> apply_decode -> sample -> write q+1 past the prompt
    boundary).  ONE definition shared by the plain slot engine
    (``_chunk_jit``) and the paged engine (``infer/paged.py``) — the
    paged-vs-plain greedy bit-parity contract cannot drift between copies
    because there are no copies.  ``caches`` is whatever cache pytree the
    caller carries (the fixed-slot pool, or the paged engine's gathered
    per-slot views)."""
    import jax
    import jax.numpy as jnp

    batch, seq = token_x.shape[0], token_x.shape[1]
    rows3 = jnp.arange(batch)[:, None, None]
    end_pos = jnp.minimum(end_pos, seq)

    def cond_fn(state):
        it, qv = state[0], state[1]
        return (it < steps) & jnp.any(qv < end_pos - 1)

    def body_fn(state):
        it, qv, token_x, caches, key, seen = state
        active = qv < end_pos - 1
        qc = jnp.clip(qv, 0, seq - 1)
        cur = jnp.take_along_axis(token_x, qc[:, None, None], axis=1)
        logits, caches = model.apply_decode(variables, cur, qc, caches,
                                            mesh=mesh)
        with jax.named_scope("sampling"):
            nxt, key = _sample_logits(logits, seen, tb, fargs, key)
            nxt = nxt.astype(token_x.dtype)
            qp1 = qc + 1
            old = jnp.take_along_axis(
                token_x, jnp.clip(qp1, 0, seq - 1)[:, None, None], axis=1)
            # write q+1 only for rows that are live AND past their own
            # prompt boundary — walking rows keep consuming their prompt
            write = active & (qp1 >= ipb)
            new = jnp.where(write[:, None, None], nxt, old)
            token_x = token_x.at[jnp.arange(batch), qp1].set(
                jnp.squeeze(new, 1), mode="drop")
        seen = seen.at[rows3, new].add(
            write.astype(jnp.float32)[:, None, None])
        qv = qv + active.astype(qv.dtype)
        return it + 1, qv, token_x, caches, key, seen

    state = (jnp.int32(0), q, token_x, caches, key, seen)
    _, q, token_x, caches, key, seen = jax.lax.while_loop(
        cond_fn, body_fn, state)
    return q, token_x, caches, key, seen


# ------------------------------------------------------ the Engine substrate

#: the Engine's chunk-program registry: every servable composition of the
#: orthogonal donated-carry components, keyed by the name the HLO/mesh
#: audits, ``budgets.json``, and ``cost_ledger.json`` know it by.  ONE
#: builder (:func:`_chunk_jit`) lowers all of them — adding a composition
#: is adding a row here, not forking a program (graft-lint's
#: ``engine-registry`` AST rule pins the no-fork invariant).  Mirrored as
#: the chunk-step tail of ``analysis/entry_points.py`` ``ENTRY_POINTS``
#: (mirrored, not imported — that module must import without jax; the
#: static-analysis tests pin the two in sync).
ENGINE_PROGRAMS: typing.Dict[str, typing.Dict[str, bool]] = {
    "engine_chunk_step": {"spec": False, "paged": False},
    "spec_chunk_step": {"spec": True, "paged": False},
    "paged_chunk_step": {"spec": False, "paged": True},
    "spec_paged_chunk_step": {"spec": True, "paged": True},
}


def program_name(spec: bool, paged: bool) -> str:
    """Registry name of the composition carrying the given components."""
    for name, parts in ENGINE_PROGRAMS.items():
        if parts["spec"] == bool(spec) and parts["paged"] == bool(paged):
            return name
    raise KeyError(f"no registered chunk program with spec={spec} "
                   f"paged={paged}")


def _spec_round(model: Model, draft_model: Model, mesh, k: int, variables,
                dvariables, q, ipb, tb, end_pos, fargs, spec_mask, fix_tok,
                fix_mask, seen_lo, token_x, caches, dcaches, key, seen):
    """One draft+verify round over whatever cache pytrees the composition
    carries — the slot pools, or the paged engine's gathered per-slot
    views: host fix splice + repetition-penalty catch-up, k+1 sequential
    draft steps, ONE width-(k+1) verify, sampled-token readback.  ONE
    definition shared by ``spec_chunk_step`` and ``spec_paged_chunk_step``,
    so the spec-vs-plain greedy parity contract cannot drift between the
    two compositions (the ``_engine_loop`` rule)."""
    import jax
    import jax.numpy as jnp

    batch, seq = token_x.shape[0], token_x.shape[1]
    rows3 = jnp.arange(batch)[:, None, None]
    end_pos = jnp.minimum(end_pos, seq)
    qc = jnp.clip(q, 0, seq - 1)
    # host accept/reject splice: the previous round's correction (or
    # bonus) token lands at the row's NEW position q — the token this
    # round's first draft step and verify offset 0 consume
    old_q = jnp.take_along_axis(token_x, qc[:, None, None], axis=1)
    fixed = jnp.where(fix_mask[:, None, None], fix_tok[:, None, :],
                      old_q)
    token_x = token_x.at[jnp.arange(batch), qc].set(
        jnp.squeeze(fixed, 1))
    # repetition-penalty catch-up for the tokens the previous round
    # emitted: count positions (seen_lo, q] at/past the prompt boundary
    # (prompt counts were seeded at admit) so `seen` again reflects the
    # full context below the write position, the plain-body invariant
    cm = ((jnp.arange(seq)[None, :, None] > seen_lo[:, None, None])
          & (jnp.arange(seq)[None, :, None] <= q[:, None, None])
          & (jnp.arange(seq)[None, :, None] >= ipb[:, None, None])
          ).astype(jnp.float32)
    seen = seen.at[rows3, token_x].add(cm)
    active = q < end_pos - 1

    # ---- draft: k+1 sequential quarter-width steps from each slot's
    # position; k greedy draft tokens written (slots at depth 0 --
    # spec_mask false -- consume but never write), the +1 step only
    # fills the draft KV row at q+k so full acceptance leaves no gap
    def dbody(i, st):
        token_x, dcaches = st
        qd = jnp.clip(q + i, 0, seq - 1)
        cur = jnp.take_along_axis(token_x, qd[:, None, None], axis=1)
        with jax.named_scope("draft"):
            dlogits, dc = draft_model.apply_decode(dvariables, cur, qd,
                                                   dcaches, mesh=mesh)
        nxt = jnp.argmax(dlogits.astype(jnp.float32), axis=-1
                         ).astype(token_x.dtype)
        qp1 = qd + 1
        old = jnp.take_along_axis(
            token_x, jnp.clip(qp1, 0, seq - 1)[:, None, None], axis=1)
        wr = active & spec_mask & (i < k) & (qp1 >= ipb)
        new = jnp.where(wr[:, None, None], nxt, old)
        token_x = token_x.at[jnp.arange(batch), qp1].set(
            jnp.squeeze(new, 1), mode="drop")
        return token_x, dc

    token_x, dcaches = jax.lax.fori_loop(0, k + 1, dbody,
                                         (token_x, dcaches))

    # ---- verify: ONE width-(k+1) full-model step scores positions
    # q..q+k per slot against the whole KV pool in a single cache read
    vidx = jnp.clip(q[:, None] + jnp.arange(k + 1), 0, seq - 1)
    vtok = jnp.take_along_axis(token_x, vidx[:, :, None], axis=1)
    with jax.named_scope("verify"):
        logits, caches = model.apply_decode(variables, vtok, qc, caches,
                                            mesh=mesh)
    with jax.named_scope("sampling"):
        vt, key = _sample_logits(logits, seen, tb, fargs, key)
        vt = vt.astype(token_x.dtype)
    return token_x, caches, dcaches, key, seen, vt


def _chunk_jit(model: Model, mesh, phase: str, *,
               draft_model: typing.Optional[Model] = None,
               k: typing.Optional[int] = None,
               paged: typing.Optional[typing.Tuple[int, int]] = None):
    """THE donated chunk-program builder — the Engine's single jit site.

    Every composition in :data:`ENGINE_PROGRAMS` lowers through this one
    function.  The donated carry is assembled from orthogonal components
    instead of forked per program: token_x + the sampling state (q/seen —
    q moves to a host-owned argument under spec) always ride; ``paged``
    swaps the fixed slot stripes for ``[num_blocks, block_tokens, ...]``
    block pools gathered/scattered through int32 read/write tables; a
    ``draft_model``/``k`` pair adds the draft cache pool and replaces the
    step loop with the shared draft+verify round at verify width k+1.
    ``phase`` is ``"init"`` (pools built in-trace), ``"admit"`` (prompt
    splice + previous-occupant eviction), or ``"plain"`` (steady state).
    One compile cache, keyed by the full composition, lives on the model
    (mirrors ``sampler._jit_sampler``).

    graft-lint pins this as the only donated chunk-program jit site in the
    tree (the ``engine-registry`` AST rule) and audits each composition's
    compiled module under its registry name: every pool leaf of every
    composition must alias input->output with no full-pool-shaped copy."""
    import jax

    from .sampler import decode_cache_shapes

    spec = draft_model is not None
    if spec == (k is None):
        raise ValueError("draft_model and k come together (the spec "
                         "component is one composable unit)")
    if phase not in ("init", "admit", "plain"):
        raise ValueError(f"unknown chunk phase {phase!r}")
    paged = None if paged is None else (int(paged[0]), int(paged[1]))
    cache = model.__dict__.setdefault("_engine_jit_cache", {})
    cache_key = (mesh, phase, id(draft_model) if spec else None,
                 None if k is None else int(k), paged)
    if cache_key in cache:
        return cache[cache_key]
    import jax.numpy as jnp

    init_caches = phase == "init"
    admit = phase in ("init", "admit")
    kk = 0 if k is None else int(k)
    if paged is not None:
        from ..model import decode as decode_mod
        from .paged import classify_cache_leaves
        bt, nb = paged

    def build_pool(shapes, info):
        """Zero pools built INSIDE the donated trace (the engine_init
        rule): a serving mesh constrains their sharding in-program, and no
        unusable host-side zero copy ever exists.  Paged leaves land at
        pool geometry; sequence-recurrent leaves stay resident per slot."""
        pools = {}
        for n, s in shapes.items():
            if paged is None or info[n][1] is None:
                pools[n] = jnp.zeros(s.shape, s.dtype)
            else:
                baxis, sax = info[n]
                ps = list(s.shape)
                ps[baxis], ps[sax] = nb, bt
                pools[n] = jnp.zeros(ps, s.dtype)
        return pools

    def gather(pools, info, rtable):
        if paged is None:
            return pools
        return {n: (decode_mod.gather_blocks(leaf, rtable, info[n][0],
                                             info[n][1])
                    if info[n][1] is not None else leaf)
                for n, leaf in pools.items()}

    def scatter(pools, views, info, wtable):
        if paged is None:
            return views
        return {n: (decode_mod.scatter_blocks(pools[n], v, wtable,
                                              info[n][0], info[n][1], bt)
                    if info[n][1] is not None else v)
                for n, v in views.items()}

    def clear_views(views, info, mask, keep_len, seq, batch):
        """Evict the previous occupant from the admitted slots' views:
        rows at/past the shared length zero (keep_len 0 — no prefix hit —
        is the slot engine's uniform clear, bit for bit); sequence-
        recurrent resident leaves clear whole-row, exactly like the plain
        admit splice."""
        out = {}
        for n, v in views.items():
            baxis, sax = info[n]
            mshape = [1] * v.ndim
            mshape[baxis] = batch
            if sax is None:
                drop = mask.reshape(mshape)
            else:
                pshape = [1] * v.ndim
                pshape[sax] = seq
                drop = (mask.reshape(mshape)
                        & (jnp.arange(seq).reshape(pshape)
                           >= keep_len.reshape(mshape)))
            out[n] = jnp.where(drop, jnp.zeros((), v.dtype), v)
        return out

    def run(variables, dvariables, q, ipb, tb, end_pos, steps, fargs,
            spec_args, admit_args, rtable, wtable, carry):
        if init_caches:
            if spec:
                token_x, key, seen = carry
            else:
                q, token_x, key, seen = carry
            pools = dpools = None
        elif spec:
            token_x, pools, dpools, key, seen = carry
        else:
            q, token_x, pools, key, seen = carry
            dpools = None
        batch, seq = token_x.shape[0], token_x.shape[1]
        info = dinfo = None
        if init_caches or paged is not None:
            shapes = decode_cache_shapes(model, variables, token_x)
            if paged is not None:
                info = classify_cache_leaves(shapes, seq)
            if spec:
                dshapes = decode_cache_shapes(draft_model, dvariables,
                                              token_x)
                if paged is not None:
                    dinfo = classify_cache_leaves(dshapes, seq)
        if init_caches:
            pools = build_pool(shapes, info)
            if spec:
                dpools = build_pool(dshapes, dinfo)
        views = gather(pools, info, rtable)
        dviews = gather(dpools, dinfo, rtable) if spec else None
        if admit:
            if paged is not None:
                mask, new_rows, keep_len = admit_args
            else:
                mask, new_rows = admit_args
                keep_len = None
            if not spec:
                # q rides the carry here (it is host state under spec):
                # admitted slots restart at the shared length (0 when not
                # paged — no prefix to resume from)
                new_q = jnp.zeros_like(q) if keep_len is None \
                    else keep_len.astype(q.dtype)
                q = jnp.where(mask, new_q, q)
            if paged is None:
                # the shared plain-engine splice clears whole cache rows
                pools_in = () if init_caches else \
                    ((views, dviews) if spec else (views,))
                token_x, seen, out = _splice_admitted(
                    token_x, seen, ipb, mask, new_rows, pools_in)
                if not init_caches:
                    if spec:
                        views, dviews = out
                    else:
                        views, = out
            else:
                token_x, seen, _ = _splice_admitted(token_x, seen, ipb,
                                                    mask, new_rows, ())
                views = clear_views(views, info, mask, keep_len, seq,
                                    batch)
                if spec:
                    dviews = clear_views(dviews, dinfo, mask, keep_len,
                                         seq, batch)
        if spec:
            spec_mask, fix_tok, fix_mask, seen_lo = spec_args
            token_x, views, dviews, key, seen, vt = _spec_round(
                model, draft_model, mesh, kk, variables, dvariables, q,
                ipb, tb, end_pos, fargs, spec_mask, fix_tok, fix_mask,
                seen_lo, token_x, views, dviews, key, seen)
            return (token_x, scatter(pools, views, info, wtable),
                    scatter(dpools, dviews, dinfo, wtable), key, seen, vt)
        q, token_x, views, key, seen = _engine_loop(
            model, mesh, variables, ipb, tb, end_pos, steps, fargs, q,
            token_x, views, key, seen)
        return q, token_x, scatter(pools, views, info, wtable), key, seen

    # four composition-specific signatures (the block tables and the spec
    # arguments appear only when their component does, so every existing
    # call convention is preserved), ONE jit call: the carry is always the
    # LAST argument and always donated — every cache-pool leaf of every
    # composition must alias input->output (graft-lint audits each
    # composition's compiled module under its ENGINE_PROGRAMS name)
    if spec and paged is not None:
        def step(variables, dvariables, q, ipb, tb, end_pos, fargs,
                 spec_mask, fix_tok, fix_mask, seen_lo, admit_args, rtable,
                 wtable, carry):
            return run(variables, dvariables, q, ipb, tb, end_pos, None,
                       fargs, (spec_mask, fix_tok, fix_mask, seen_lo),
                       admit_args, rtable, wtable, carry)
        donate = 14
    elif spec:
        def step(variables, dvariables, q, ipb, tb, end_pos, fargs,
                 spec_mask, fix_tok, fix_mask, seen_lo, admit_args, carry):
            return run(variables, dvariables, q, ipb, tb, end_pos, None,
                       fargs, (spec_mask, fix_tok, fix_mask, seen_lo),
                       admit_args, None, None, carry)
        donate = 12
    elif paged is not None:
        def step(variables, ipb, tb, end_pos, steps, fargs, admit_args,
                 rtable, wtable, carry):
            return run(variables, None, None, ipb, tb, end_pos, steps,
                       fargs, None, admit_args, rtable, wtable, carry)
        donate = 9
    else:
        def step(variables, ipb, tb, end_pos, steps, fargs, admit_args,
                 carry):
            return run(variables, None, None, ipb, tb, end_pos, steps,
                       fargs, None, admit_args, None, None, carry)
        donate = 7
    cache[cache_key] = jax.jit(step, donate_argnums=(donate,))
    return cache[cache_key]


class Engine:
    """ONE serving engine, composed per deployment.

    Owns the mesh, the donation discipline, and the compile cache for the
    registered chunk programs (:data:`ENGINE_PROGRAMS`): an executor holds
    an Engine describing WHICH orthogonal carry components its deployment
    assembles — the draft pool + verify width via ``draft_model``/``k``,
    the block tables via ``paged=(block_tokens, num_blocks)`` — and
    fetches each phase's compiled program from it.  Spec-on-paged is a
    composition handed to the one builder, not a fourth forked program;
    dropping a component (the speculative self-disable) is recomposition,
    not a carry-layout migration hand-written per pair.  ``name`` is the
    registry/audit name ``budgets.json``, ``cost_ledger.json``, and the
    mesh audit key this composition's rows by."""

    def __init__(self, model: Model, mesh, *,
                 draft_model: typing.Optional[Model] = None,
                 k: typing.Optional[int] = None,
                 paged: typing.Optional[typing.Tuple[int, int]] = None):
        self.model = model
        self.mesh = mesh
        self.draft_model = draft_model
        self.k = None if k is None else int(k)
        self.paged = None if paged is None else (int(paged[0]),
                                                 int(paged[1]))
        self.name = program_name(spec=draft_model is not None,
                                 paged=paged is not None)

    @property
    def components(self) -> typing.Dict[str, bool]:
        """The composition's registry row (``{"spec": ..., "paged": ...}``)."""
        return dict(ENGINE_PROGRAMS[self.name])

    def step(self, phase: str):
        """The composition's compiled donated program for ``phase``
        (``"init"``/``"admit"``/``"plain"``)."""
        return _chunk_jit(self.model, self.mesh, phase,
                          draft_model=self.draft_model, k=self.k,
                          paged=self.paged)


class EngineExecutor:
    """Device half of the continuous engine: the slot pool, its host-side
    argument mirrors, and the donated dispatch.

    Raises ``NotImplementedError`` at construction for models the stepped
    decode path cannot serve (video mode, layers without a streaming form)
    — ``rest_api`` falls back to the batch engine on that signal.
    """

    def __init__(self, interface, slots: int,
                 seed: typing.Optional[int] = None):
        import jax
        import jax.numpy as jnp

        from .sampler import decode_cache_bytes, decode_cache_shapes

        p: ModelParameter = interface.params
        if p.use_video or not p.use_language:
            raise NotImplementedError("the continuous engine decodes text "
                                      "(gpt-mode) models only")
        self.interface = interface
        self.slots = int(slots)
        self.params_w, self.model_w = interface._model_for_width(self.slots)
        self.variables = interface.variables
        self.mesh = interface.mesh
        self.seq = p.sequence_length // p.token_patch_size
        self.tps = p.token_patch_size
        probe = np.zeros((self.slots, self.seq, self.tps), np.int32)
        # probes the streaming form now (NotImplementedError -> batch
        # fallback) and pins the pool's byte size for the bandwidth gauges
        self.cache_bytes = decode_cache_bytes(self.model_w, self.variables,
                                              probe)
        # ALSO trace one decode step with a VECTOR position, abstractly:
        # the per-slot-only guards (batch-less KV layouts _batch_leading
        # cannot broadcast in place, multi-axis position embeddings, a
        # vector-trace cache layout diverging from the scalar-derived pool)
        # fire inside the step trace, not in the shape probe above — they
        # must fail CONSTRUCTION so serve_engine="auto" falls back to the
        # batch engine instead of 500ing every dispatch forever
        shapes = decode_cache_shapes(self.model_w, self.variables, probe)
        aval = jax.ShapeDtypeStruct
        jax.eval_shape(
            lambda v, t, c: self.model_w.apply_decode(
                v, t, jnp.zeros(self.slots, jnp.int32), c, mesh=self.mesh),
            self.variables, aval((self.slots, 1, self.tps), jnp.int32),
            {k: aval(v.shape, v.dtype) for k, v in shapes.items()})
        # per-slot dispatch arguments (host mirrors; idle slots are inert:
        # end_pos 0 never activates)
        self.ipb = np.full(self.slots, self.seq - 1, np.int32)
        self.tb = np.zeros(self.slots, np.float32)
        self.end_pos = np.zeros(self.slots, np.int32)
        self.top_k = np.full(self.slots, int(p.sampling_top_k), np.int32)
        self.top_p = np.full(self.slots, float(p.sampling_top_p), np.float32)
        self.rep = np.full(self.slots,
                           float(p.sampling_repetition_penalty), np.float32)
        self.q = np.zeros(self.slots, np.int64)
        self._defaults = (int(p.sampling_top_k), float(p.sampling_top_p),
                          float(p.sampling_repetition_penalty))
        self._admit_mask = np.zeros(self.slots, bool)
        self._admit_rows = np.zeros((self.slots, self.seq, self.tps),
                                    np.int32)
        self._token_host = np.zeros((self.slots, self.seq, self.tps),
                                    np.int32)
        self._carry = None
        self._key0 = jax.random.PRNGKey(p.data_seed if seed is None
                                        else seed)
        # prompt padding beyond each admitted row mirrors the batch path's
        # pad_random convention (inert under causal masking — parity
        # surface only); seeded so reruns are reproducible
        self._pad_rng = np.random.default_rng(p.data_seed)
        self._jnp = jnp
        #: the deployment's composition — subclasses recompose with their
        #: components (draft pool, block tables) after their own setup
        self.engine = Engine(self.model_w, self.mesh)

    # -- slot staging --------------------------------------------------------

    def admit(self, slot: int, req) -> None:
        """Stage ``req`` (an ``infer.scheduler.EngineRequest``) into
        ``slot``; takes effect inside the next dispatch's admit splice."""
        p = self.params_w
        row = self._pad_rng.integers(0, p.vocab_size,
                                     (self.seq, self.tps)).astype(np.int32)
        toks = np.asarray(req.toks, np.int32).reshape(-1)[:self.seq - 1]
        row[:len(toks), :] = toks[:, None]
        if len(toks) == 0:
            # _kv_prep parity: an empty prompt's position 0 is zeroed (the
            # full sampler's first iteration writes 0 there)
            row[0, :] = 0
        self._admit_rows[slot] = row
        self._admit_mask[slot] = True
        self.ipb[slot] = len(toks)
        self.tb[slot] = float(req.temperature)
        self.end_pos[slot] = req.end_pos(self.seq)
        tk, tp, rp = self._defaults
        self.top_k[slot] = int(req.top_k) if req.top_k is not None else tk
        self.top_p[slot] = float(req.top_p) if req.top_p is not None else tp
        self.rep[slot] = (float(req.rep_penalty)
                          if req.rep_penalty is not None else rp)
        self.q[slot] = 0

    def release(self, slot: int) -> None:
        """Park a finished/evicted slot: inert until the next admission."""
        self.end_pos[slot] = 0
        self.ipb[slot] = self.seq - 1
        self._admit_mask[slot] = False

    # -- dispatch ------------------------------------------------------------

    def dispatch(self, steps: int) -> np.ndarray:
        """Run one donated chunk (up to ``steps`` iterations per slot; the
        compiled loop exits early once every live slot reaches its end).
        Returns the post-chunk position vector; ``tokens()`` serves rows
        from the same read-back.  Any exception leaves the donated carry
        unusable — callers must ``reset()`` (the controller does)."""
        jnp = self._jnp
        phase = ("init" if self._carry is None else
                 "admit" if self._admit_mask.any() else "plain")
        fn = self.engine.step(phase)
        fargs = (jnp.asarray(self.top_k), jnp.asarray(self.top_p),
                 jnp.asarray(self.rep))
        if phase == "init":
            seen = jnp.zeros((self.slots, self.params_w.vocab_size),
                             jnp.float32)
            carry = (jnp.zeros(self.slots, jnp.int32),
                     jnp.asarray(self._token_host), self._key0, seen)
        else:
            carry = self._carry
        admit_args = ()
        if phase != "plain":
            admit_args = (jnp.asarray(self._admit_mask),
                          jnp.asarray(self._admit_rows))
        out = fn(self.variables, jnp.asarray(self.ipb), jnp.asarray(self.tb),
                 jnp.asarray(self.end_pos), jnp.int32(int(steps)), fargs,
                 admit_args, carry)
        q, token_x = out[0], out[1]
        self._carry = out
        # one small D2H per chunk (positions + tokens, never the pool):
        # end detection and answer extraction read these
        self._token_host = np.asarray(token_x)
        self.q = np.asarray(q).astype(np.int64)
        self._admit_mask[:] = False
        return self.q

    def tokens(self, slot: int) -> np.ndarray:
        """The slot's token row from the last dispatch read-back, sliced to
        its own end (lane 0, matching ``complete_tokens``'s return)."""
        end = int(self.end_pos[slot])
        return self._token_host[slot, :end, 0]

    def reset(self) -> None:
        """Drop the pool (next dispatch re-initialises it in-trace) and
        park every slot — the recovery path after a failed dispatch."""
        # pool re-inits are incident evidence (a failed dispatch answered
        # every resident 500): into the flight recorder, off the hot path
        from ..telemetry import events as _flight
        _flight.record("engine_reset", slots=int(self.slots))
        self._carry = None
        self._admit_mask[:] = False
        self.end_pos[:] = 0
        self.ipb[:] = self.seq - 1
        self.q[:] = 0


class SpecEngineExecutor(EngineExecutor):
    """Draft-and-verify executor: the slot engine with a second
    (quarter-width) cache pool and the host accept loop.

    ``draft`` is an ``infer.spec`` triple ``(params, model, variables)``.
    Construction raises for deployments speculation cannot serve — a draft
    whose vocabulary/sequence geometry differs from the target, or EITHER
    model carrying sequence-recurrent decode caches (cumsum/conv state the
    rollback-by-overwrite argument cannot heal; probed here with an
    abstract width-2 verify trace so ``spec_decode="auto"`` falls back to
    the plain engine at construction instead of 500ing every dispatch).

    Greedy parity contract: emitted tokens are accepted drafts (which, by
    the accept rule, EQUAL the verify's argmax) and the verify's own argmax
    at the first mismatch — so the output stream is exactly the target
    model's greedy walk, bit-identical to the plain engine
    (tests/spec_decode_test.py pins it token-for-token, including through
    a total-rejection draft).
    """

    #: sliding acceptance window: self-disable consults the last N verify
    #: rounds once they cover at least MIN_DRAFTED drafted tokens
    WINDOW_ROUNDS = 64
    MIN_DRAFTED = 16

    def __init__(self, interface, slots: int, draft,
                 seed: typing.Optional[int] = None,
                 draft_tokens: typing.Optional[int] = None,
                 min_accept_rate: typing.Optional[float] = None):
        super().__init__(interface, slots, seed=seed)
        self._init_spec(draft, draft_tokens, min_accept_rate)

    def _init_spec(self, draft,
                   draft_tokens: typing.Optional[int] = None,
                   min_accept_rate: typing.Optional[float] = None) -> None:
        """Attach the spec component to an already-built executor: draft
        pool, host accept state, and the recomposed Engine.  Factored out
        of ``__init__`` so ``SpecPagedEngineExecutor`` can stack it on top
        of the paged base — the composition IS the two init halves run in
        sequence, mirroring the carry."""
        import collections

        import jax

        from . import spec as spec_mod
        from .sampler import decode_cache_shapes

        interface = self.interface
        p: ModelParameter = interface.params
        # knobs ride explicit arguments so the caller's RESOLVED params win
        # (rest_api._resolve_engine serves a params object that may differ
        # from interface.params — the slots pattern); interface.params is
        # only the fallback for direct construction
        self.k = int(getattr(p, "spec_draft_tokens", 4)
                     if draft_tokens is None else draft_tokens)
        self.spec_min_accept = float(
            getattr(p, "spec_min_accept_rate", 0.0)
            if min_accept_rate is None else min_accept_rate)
        if self.k + 1 >= self.seq:
            raise NotImplementedError(
                f"spec_draft_tokens={self.k} needs a verify width under the "
                f"sequence length {self.seq}")
        spec_mod.check_draft_compatible(p, draft[0])
        self.draft_params_w, self.draft_model_w, self.draft_variables = \
            spec_mod.draft_for_width(draft, self.slots)
        # abstract width-2 verify probe of BOTH models: multi-position
        # support and the no-recurrent-caches rollback contract must fail
        # CONSTRUCTION (auto -> plain engine), not the first dispatch
        aval = jax.ShapeDtypeStruct
        jnp = self._jnp
        probe = np.zeros((self.slots, self.seq, self.tps), np.int32)
        for m, v in ((self.model_w, self.variables),
                     (self.draft_model_w, self.draft_variables)):
            shapes = decode_cache_shapes(m, v, probe)
            jax.eval_shape(
                lambda vv, t, c, mm=m: mm.apply_decode(
                    vv, t, jnp.zeros(self.slots, jnp.int32), c,
                    mesh=self.mesh),
                v, aval((self.slots, 2, self.tps), jnp.int32),
                {n: aval(s.shape, s.dtype) for n, s in shapes.items()})
        #: per-slot draft depth (k or 0 — scheduler.spec_depth); all False
        #: once the acceptance self-disable fires
        self._spec_mask = np.zeros(self.slots, bool)
        self._fix_tok = np.zeros((self.slots, self.tps), np.int32)
        self._fix_mask = np.zeros(self.slots, bool)
        self._seen_lo = np.zeros(self.slots, np.int32)
        self._spec_enabled = True
        self._events: typing.List[dict] = []
        self._window = collections.deque(maxlen=self.WINDOW_ROUNDS)
        self.drafted_total = 0
        self.accepted_total = 0
        # device mirrors of the slot-staging arguments: they change only at
        # admit/release, and re-uploading all of them every round is
        # measurable host overhead next to a multi-token verify round
        self._dev_args = None
        # recompose with the draft pool on top of whatever the base built
        # (plain slots, or the paged component's block tables)
        self.engine = Engine(self.model_w, self.mesh,
                             draft_model=self.draft_model_w, k=self.k,
                             paged=self.engine.paged)

    # -- slot staging --------------------------------------------------------

    def admit(self, slot: int, req) -> None:
        from .scheduler import spec_depth
        super().admit(slot, req)
        self._spec_mask[slot] = (self._spec_enabled and
                                 spec_depth(req, self._defaults, self.k) > 0)
        self._fix_mask[slot] = False
        self._seen_lo[slot] = 0
        self._dev_args = None

    def release(self, slot: int) -> None:
        super().release(slot)
        self._spec_mask[slot] = False
        self._fix_mask[slot] = False
        self._dev_args = None

    # -- dispatch ------------------------------------------------------------

    def dispatch(self, steps: int) -> np.ndarray:
        """Acceptance-aware dispatch: the controller's iteration budget
        converts to verify ROUNDS (each advances a slot by 1..k+1 tokens);
        once self-disabled, every dispatch delegates to the plain donated
        chunk program on the target pool."""
        if not self._spec_enabled:
            return super().dispatch(steps)
        jnp = self._jnp
        rounds = max(1, -(-int(steps) // (self.k + 1)))
        for _ in range(rounds):
            phase = ("init" if self._carry is None else
                     "admit" if self._admit_mask.any() else "plain")
            fn = self.engine.step(phase)
            if self._dev_args is None:
                # slot-staging arguments change only at admit/release: keep
                # their device copies across rounds (the per-round uploads
                # are just q / the fix splice / seen_lo)
                self._dev_args = (jnp.asarray(self.ipb),
                                  jnp.asarray(self.tb),
                                  jnp.asarray(self.end_pos),
                                  (jnp.asarray(self.top_k),
                                   jnp.asarray(self.top_p),
                                   jnp.asarray(self.rep)),
                                  jnp.asarray(self._spec_mask))
            ipb_d, tb_d, end_d, fargs, mask_d = self._dev_args
            if phase == "init":
                seen = jnp.zeros((self.slots, self.params_w.vocab_size),
                                 jnp.float32)
                carry = (jnp.asarray(self._token_host), self._key0, seen)
            else:
                carry = self._carry
            admit_args = ()
            if phase != "plain":
                admit_args = (jnp.asarray(self._admit_mask),
                              jnp.asarray(self._admit_rows))
            out = fn(self.variables, self.draft_variables,
                     jnp.asarray(self.q.astype(np.int32)),
                     ipb_d, tb_d, end_d, fargs, mask_d,
                     jnp.asarray(self._fix_tok),
                     jnp.asarray(self._fix_mask),
                     jnp.asarray(self._seen_lo), admit_args, carry)
            self._carry = out[:5]
            # per-round D2H: tokens + the verify's sampled tokens (the
            # accept decision is host-side carry state between chunks).
            # np.array, not asarray: the accept loop WRITES corrections
            # into this mirror, and asarray of a device buffer is read-only
            self._token_host = np.array(out[0])
            self._admit_mask[:] = False
            self._accept_round(np.asarray(out[5]))
            if not self._spec_enabled:
                break  # self-disabled mid-dispatch: plain takes over
            if not np.any((self.end_pos > 0)
                          & (self.q < self.end_pos - 1)):
                break  # every live slot reached its end
        return self.q

    # -- host accept loop ----------------------------------------------------

    def _accept_round(self, t: np.ndarray) -> None:
        """Longest-accepted-prefix per slot: walk the verify's k+1 sampled
        tokens against the drafted ``token_x`` rows, auto-advancing through
        prompt positions (chunked prefill at k+1 tokens/round rides the
        same verify), and stage the correction/bonus token as the next
        round's fix splice."""
        k, seq = self.k, self.seq
        self._fix_mask[:] = False
        for s in range(self.slots):
            q0, end = int(self.q[s]), int(min(self.end_pos[s], seq))
            self._seen_lo[s] = q0
            if end <= 0 or q0 >= end - 1:
                continue  # parked / finished: inert
            ipb = int(self.ipb[s])
            spec_ok = bool(self._spec_mask[s])
            adv = 0
            drafted = accepted = 0
            for j in range(k + 1):
                p = q0 + 1 + j
                if p > end - 1:
                    break  # the slot's decode extent caps acceptance
                if p < ipb:
                    adv += 1  # prompt walk: the verify consumed the real
                    continue  # prompt token, nothing to compare or write
                tok = t[s, j]
                if j < k and spec_ok:
                    drafted += 1
                    if np.array_equal(self._token_host[s, p], tok):
                        accepted += 1
                        adv += 1
                        continue
                # first mismatch (the verify's own token corrects it), the
                # bonus token after k accepted drafts, or a depth-0 slot's
                # one sampled token — emit and stop: positions beyond a
                # correction hold rejected drafts
                self._fix_tok[s] = tok
                self._fix_mask[s] = True
                self._token_host[s, p] = tok
                adv += 1
                break
            self.q[s] = q0 + adv
            if drafted:
                self.drafted_total += drafted
                self.accepted_total += accepted
                self._window.append((accepted, drafted))
                self._events.append({"kind": "verify", "slot": s,
                                     "accepted": accepted,
                                     "drafted": drafted, "emitted": adv})
        self._maybe_self_disable()

    def _maybe_self_disable(self) -> None:
        if not self._spec_enabled or self.spec_min_accept <= 0:
            return
        drafted = sum(d for _, d in self._window)
        if len(self._window) < 8 or drafted < self.MIN_DRAFTED:
            return
        rate = sum(a for a, _ in self._window) / drafted
        if rate >= self.spec_min_accept:
            return
        # a workload the draft cannot predict must degrade to plain-speed
        # serving, not crawl through rejected drafts: log loudly, emit the
        # metric event, and permanently revert to the plain chunk program
        print("WARNING: speculative decoding self-disabled — sliding-window "
              f"acceptance {rate:.3f} < spec_min_accept_rate "
              f"{self.spec_min_accept} over {drafted} drafted tokens; "
              "serving continues on the plain continuous engine",
              flush=True)
        self._events.append({"kind": "disabled", "rate": rate,
                             "drafted": drafted})
        from ..telemetry import events as _flight
        _flight.record("spec_disabled", accept_rate=round(rate, 4),
                       drafted=int(drafted))
        self._spec_enabled = False
        self._spec_mask[:] = False
        self._to_plain_carry()

    def _to_plain_carry(self) -> None:
        """Drop the spec component from the composition: the Engine
        recomposes without the draft pool (the remaining components — plain
        slots or block tables — keep their layout), and the carry converts
        to the recomposed program's shape.  The host token mirror already
        holds every emitted token (including corrections the device never
        saw), so token_x re-uploads from it; ``seen`` gets the same
        host-side catch-up the next spec round would have applied; the
        draft pool is dropped (freed)."""
        self.engine = Engine(self.model_w, self.mesh,
                             paged=self.engine.paged)
        if self._carry is None or len(self._carry) != 5:
            return
        jnp = self._jnp
        _, caches, _, key, seen = self._carry
        seen_np = np.array(seen)  # copy: device buffers read back read-only
        for s in range(self.slots):
            lo, hi = int(self._seen_lo[s]), int(self.q[s])
            ipb = int(self.ipb[s])
            for p in range(max(lo + 1, ipb, 1), hi + 1):
                if p < self.seq:
                    for lane in self._token_host[s, p]:
                        seen_np[s, int(lane)] += 1.0
        self._fix_mask[:] = False
        self._carry = (jnp.asarray(self.q.astype(np.int32)),
                       jnp.asarray(self._token_host), caches, key,
                       jnp.asarray(seen_np))

    # -- observability -------------------------------------------------------

    def take_spec_events(self) -> typing.List[dict]:
        """Drain the per-verify accept events (scheduler forwards them as
        hooks, rest_api turns them into the hbnlp_spec_* series)."""
        out, self._events = self._events, []
        return out

    def spec_summary(self) -> dict:
        """Ops surface for /health: the acceptance economics at a glance."""
        drafted = max(1, self.drafted_total)
        return {"enabled": bool(self._spec_enabled),
                "draft_tokens": self.k,
                "drafted": int(self.drafted_total),
                "accepted": int(self.accepted_total),
                "accept_rate": round(self.accepted_total / drafted, 4)}

    def reset(self) -> None:
        super().reset()
        self._fix_mask[:] = False
        self._spec_mask[:] = False
        self._seen_lo[:] = 0
        self._dev_args = None  # reset parks every slot: end_pos changed
