"""Lowering registry: every jitted entry point the HLO passes audit.

The first audit covered ONE entry point (the decode chunk step); the train
step (``train/__init__.py`` ``donate_argnums=(0,)``), the
cache-initialising first decode chunk ("prefill entry"), and the eval fn
were on the honor system.  This module builds a small audit model and
lowers + compiles all four on the CURRENT backend — on TPU that audits the
exact production executable; under the CPU rig it pins the structural
properties (donation, aliasable carries, collective count, no host syncs)
that the TPU compile inherits.

Each ``lower_*`` returns ``(hlo_text, context)`` where ``context`` carries
what the passes need: ``donated_leaves`` (expected alias count),
``protected`` (shapes whose full-buffer copy is a regression), and
``bf16_params`` for the dtype-promotion pass.  ``audit_all`` runs every
pass over every entry point against ``analysis/budgets.json``.

jax is imported inside functions only — importing this module stays cheap
(and safe from the AST-only consumers of the package).
"""
from __future__ import annotations

import typing

from . import hlo_lint

#: the audit model: small enough that all four compiles finish in seconds
#: on one CPU, in bf16 so the dtype-promotion pass has teeth (a param-
#: shaped f32 convert in a bf16 forward is an accidental master-weight
#: copy).  Mirrors tests/backend.py's harness config.
AUDIT_CONFIG: typing.Dict[str, typing.Any] = {
    "model_mode": "gpt", "use_video": False, "use_language": True,
    "sequence_length": 16, "features_per_head": 16, "heads": 2,
    "depth": 2, "train_batch_size": 4, "vocab_size": 32,
    "group_linear_factor": 2,
    "intermediate_feed_forward_multiplier_multiplier": 0.5,
    "calculation_dtype": "bfloat16", "storage_dtype": "bfloat16",
    "memory_reduction_strategy": "none",
    # the flagship optimizer chain (configs/32big_mixer.json): its
    # sm3/momentum slots put real optimizer state into the donated carry, so
    # the donation audit covers opt-state aliasing too, not just params
    "optimizer": "adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate",
    "block_config": [
        {"layer": ["norm-shift-scale-features-group",
                   "bottleneck_group_linear-in:relu-mid:relu-mid:norm-mid:"
                   "shift-mid:scale-mid:features"]},
        {"layer": ["norm-shift-scale-features-group",
                   "attention-biased_attention_map-absolute-input_as_value-"
                   "shared",
                   "norm-shift-scale-features-group", "activation-gelu",
                   "attention-biased_attention_map-absolute-input_as_value-"
                   "shared"]}],
}

#: audited entry points, in budgets.json key order.  The four ``*_chunk_
#: step`` tails mirror ``infer/engine.py`` ``ENGINE_PROGRAMS`` — the
#: Engine's composition registry (mirrored, not imported: this module must
#: import without jax; the static-analysis tests pin the two in sync)
ENTRY_POINTS = ("train_step", "decode_chunk_step", "prefill_entry_step",
                "eval_fn", "engine_chunk_step", "spec_chunk_step",
                "paged_chunk_step", "spec_paged_chunk_step")

#: KV block size for the paged-engine audit: a real multi-block geometry
#: (seq 16 -> 4 blocks/slot) so the table gather/scatter machinery is
#: present in the audited module, not degenerate single-block paging
PAGED_AUDIT_BLOCK_TOKENS = 4

#: the speculative DRAFT at audit scale: the same model definition at a
#: smaller width (the one-graph-many-layouts rule the production draft
#: config follows; features_per_head 8 is the narrowest width the audit
#: architecture's factorized vocab supports)
DRAFT_AUDIT_OVERRIDES: typing.Dict[str, typing.Any] = {
    "features_per_head": 8}


def build_audit_model(overrides: typing.Optional[dict] = None, seed: int = 0):
    """(params, model, variables, token_x, batch) at the audit config."""
    import jax.numpy as jnp
    import numpy as np

    from ..config import ModelParameter
    from ..model import Model

    cfg = dict(AUDIT_CONFIG)
    cfg.update(overrides or {})
    params = ModelParameter(cfg)
    model = Model(params)
    rng = np.random.default_rng(seed)
    seq = params.sequence_dim.size
    tps = params.token_patch_dim.size
    token_x = rng.integers(0, params.vocab_size,
                           (params.train_batch_size, seq, tps)
                           ).astype(np.int32)
    batch = {"token_x": jnp.asarray(token_x),
             "token_y": jnp.asarray(token_x)}
    variables = {k: jnp.asarray(v) for k, v in model.init(batch).items()}
    return params, model, variables, token_x, batch


# ---- entry-point lowerings -------------------------------------------------

def make_trainer(params, model, batch):
    """One ``(trainer, state)`` shared by every train-side lowering —
    ``init_state`` materialises params + optimizer state, so ``audit_all``
    pays it once instead of per entry point."""
    from ..train import Trainer

    trainer = Trainer(params, model)
    return trainer, trainer.init_state(batch)


def lower_train_step(params, model, variables, batch, donate: bool = True,
                     trainer=None, state=None):
    """Compiled donated train step.  ``donate=False`` compiles the same
    step UNdonated — the negative control proving the donation audit bites
    on real HLO, not only on synthetic text."""
    import jax

    if trainer is None:
        trainer, state = make_trainer(params, model, batch)
    if donate:
        lowered = trainer.lowered(state, batch)
    else:
        lowered = trainer._build_step(donate=False).lower(
            state, batch, jax.random.PRNGKey(0))
    compiled = lowered.compile()
    hlo = compiled.as_text()
    leaves = jax.tree_util.tree_leaves(state)
    context = {
        "donated_leaves": len(leaves) if donate else 0,
        # a full copy of any param/optimizer-state leaf is the train-side
        # analogue of the full-cache decode copy (2x HBM on the biggest
        # buffers in the program)
        "protected": hlo_lint.shape_strings(
            {str(i): leaf for i, leaf in enumerate(leaves)}, min_rank=2),
        "donated_bytes": sum(leaf.size * leaf.dtype.itemsize
                             for leaf in leaves),
        "state": state,
        "compiled": compiled,
        # jaxpr thunk for the cost ledger's analytical per-scope counts —
        # tracing is cheap next to the compile above, and only the ledger
        # pays it.  Undonated: donation changes aliasing, never flops.
        "trace": lambda: trainer._build_step(donate=False).trace(
            state, batch, jax.random.PRNGKey(0)).jaxpr,
    }
    return hlo, context


def lower_eval_fn(params, model, variables, batch, trainer=None, state=None):
    """Compiled forward-only eval fn (no donation expected — variables are
    reused across eval batches; the audit pins collectives + host syncs +
    bf16 discipline)."""
    if trainer is None:
        trainer, state = make_trainer(params, model, batch)
    compiled = trainer.lowered_eval(state, batch).compile()
    hlo = compiled.as_text()
    context = {
        "donated_leaves": 0,
        "bf16_params": hlo_lint.shape_strings(variables, min_rank=2,
                                              dtypes={"bf16"}),
        "compiled": compiled,
        "trace": lambda: trainer._eval_fn.trace(state.variables,
                                                batch).jaxpr,
    }
    return hlo, context


def lower_decode_step(model, variables, token_x, logits_filter: bool = False,
                      mesh=None):
    """Compiled donated decode chunk step (the PR 2 property: every cache
    leaf aliased, no full-cache-shaped copy).

    Uses the zero-cache layout from ``decode_cache_shapes`` (the layout the
    stepped driver carries) and abstract avals throughout: ``lower()``
    needs shapes/dtypes only, and materialising the caches would allocate
    the multi-GB buffers this check exists to police — running it next to
    a live serving deployment must not OOM the chip.
    """
    import jax
    import jax.numpy as jnp

    from ..infer.sampler import decode_cache_shapes, make_kv_step

    aval = jax.ShapeDtypeStruct
    batch = token_x.shape[0]
    shapes = decode_cache_shapes(model, variables, token_x)
    caches = {k: aval(v.shape, v.dtype) for k, v in shapes.items()}
    step = jax.jit(make_kv_step(model, mesh=mesh,
                                logits_filter=logits_filter),
                   donate_argnums=(6,))
    scalar = aval((), jnp.int32)
    fargs = _filter_args(batch, logits_filter)
    key = aval(jax.random.PRNGKey(0).shape, jnp.uint32)
    carry = (scalar, aval(tuple(token_x.shape), token_x.dtype), caches, key)
    if logits_filter:
        carry = carry + (aval((batch, model.params.vocab_size),
                              jnp.float32),)
    args = (variables, aval((batch,), jnp.int32),
            aval((batch,), jnp.float32), scalar, scalar, fargs, carry)
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    # the donated carry has EXACTLY len(shapes) cache leaves + q + token_x
    # + key (+ seen under the filter); requiring that many aliases means
    # every leaf aliased — a count any cache leaf could miss only by
    # another, nonexistent leaf standing in for it
    context = {
        "donated_leaves": len(shapes) + 3 + (1 if logits_filter else 0),
        "protected": hlo_lint.shape_strings(shapes, key_filter="/kv"),
        "cache_shapes": shapes,
        "bf16_params": hlo_lint.shape_strings(variables, min_rank=2,
                                              dtypes={"bf16"}),
        "compiled": compiled,
        "trace": lambda: step.trace(*args).jaxpr,
    }
    return hlo, context


def lower_prefill_entry(model, variables, token_x,
                        logits_filter: bool = False, mesh=None,
                        donate: bool = True):
    """Compiled cache-initialising first chunk (``kv_step_init`` — the
    entry the prefill/steady split hands the donated carry to).  Its carry
    omits the caches (built in-trace, mesh-constrained by the first decode
    step) but q/token_x/key (+ seen) are still donated and must alias.

    ``donate=False`` compiles the same step UNdonated — the negative
    control for this entry point's donation audit.  The returned context
    keeps the donated-case expectation either way, so the control asserts
    the audit FLAGS the undonated module against it."""
    import jax
    import jax.numpy as jnp

    from ..infer.sampler import decode_cache_shapes, make_kv_step

    aval = jax.ShapeDtypeStruct
    batch = token_x.shape[0]
    shapes = decode_cache_shapes(model, variables, token_x)
    step = jax.jit(make_kv_step(model, mesh=mesh,
                                logits_filter=logits_filter,
                                init_caches=True),
                   donate_argnums=(6,) if donate else ())
    scalar = aval((), jnp.int32)
    fargs = _filter_args(batch, logits_filter)
    key = aval(jax.random.PRNGKey(0).shape, jnp.uint32)
    carry = (scalar, aval(tuple(token_x.shape), token_x.dtype), key)
    if logits_filter:
        carry = carry + (aval((batch, model.params.vocab_size),
                              jnp.float32),)
    args = (variables, aval((batch,), jnp.int32),
            aval((batch,), jnp.float32), scalar, scalar, fargs, carry)
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    context = {
        "donated_leaves": 3 + (1 if logits_filter else 0),
        "protected": hlo_lint.shape_strings(shapes, key_filter="/kv"),
        "bf16_params": hlo_lint.shape_strings(variables, min_rank=2,
                                              dtypes={"bf16"}),
        "compiled": compiled,
        "trace": lambda: step.trace(*args).jaxpr,
    }
    return hlo, context


def lower_engine_step(model, variables, token_x, mesh=None):
    """Compiled donated continuous-batching engine chunk step — the
    slot-pool analogue of ``decode_chunk_step``: the donated carry holds the
    ENTIRE fixed-slot KV pool (per-slot rows of every cache leaf), and the
    audit pins that every pool leaf aliases input->output with no
    full-pool-shaped copy, per-slot position vector and all
    (infer/engine.py; docs/SERVING.md).

    Audits the steady-state ``plain`` phase — the program every
    decode chunk between admissions runs; abstract avals throughout, same
    OOM-safety argument as ``lower_decode_step``.
    """
    import jax
    import jax.numpy as jnp

    from ..infer.engine import _chunk_jit
    from ..infer.sampler import decode_cache_shapes

    aval = jax.ShapeDtypeStruct
    batch = token_x.shape[0]
    shapes = decode_cache_shapes(model, variables, token_x)
    caches = {k: aval(v.shape, v.dtype) for k, v in shapes.items()}
    step = _chunk_jit(model, mesh, "plain")
    vec_i = aval((batch,), jnp.int32)
    vec_f = aval((batch,), jnp.float32)
    scalar = aval((), jnp.int32)
    key = aval(jax.random.PRNGKey(0).shape, jnp.uint32)
    seen = aval((batch, model.params.vocab_size), jnp.float32)
    carry = (vec_i, aval(tuple(token_x.shape), token_x.dtype), caches, key,
             seen)
    fargs = (vec_i, vec_f, vec_f)
    args = (variables, vec_i, vec_f, vec_i, scalar, fargs, (), carry)
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    context = {
        # q + token_x + key + seen ride the donated carry next to the pool
        "donated_leaves": len(shapes) + 4,
        "protected": hlo_lint.shape_strings(shapes, key_filter="/kv"),
        "cache_shapes": shapes,
        "bf16_params": hlo_lint.shape_strings(variables, min_rank=2,
                                              dtypes={"bf16"}),
        "compiled": compiled,
        "trace": lambda: step.trace(*args).jaxpr,
    }
    return hlo, context


def lower_paged_step(model, variables, token_x, mesh=None):
    """Compiled donated PAGED engine chunk step (``infer/engine.py``
    ``_chunk_jit`` phase ``plain`` with the ``paged`` component): the
    donated carry holds the KV BLOCK POOLS (per-leaf ``[num_blocks, block_tokens, ...]`` layouts plus
    any resident recurrent leaves), and the chunk gathers per-slot views
    through the read table, runs the shared engine loop, and scatters back
    through the write table.  The audit pins every pool leaf aliased
    input->output with no full-pool-shaped copy — the gather/scatter
    round-trip must not cost a resident duplicate of the pool.

    Abstract avals throughout, same OOM-safety argument as
    ``lower_decode_step``."""
    import jax
    import jax.numpy as jnp

    from ..infer.engine import _chunk_jit
    from ..infer.paged import classify_cache_leaves
    from ..infer.sampler import decode_cache_shapes

    aval = jax.ShapeDtypeStruct
    batch, seq = token_x.shape[0], token_x.shape[1]
    bt = PAGED_AUDIT_BLOCK_TOKENS if seq % PAGED_AUDIT_BLOCK_TOKENS == 0 \
        else 1
    seq_blocks = seq // bt
    num_blocks = batch * seq_blocks
    shapes = decode_cache_shapes(model, variables, token_x)
    info = classify_cache_leaves(shapes, seq)
    pools = {}
    for n, s in shapes.items():
        baxis, sax = info[n]
        if sax is None:
            pools[n] = aval(tuple(s.shape), s.dtype)
        else:
            ps = list(s.shape)
            ps[baxis], ps[sax] = num_blocks, bt
            pools[n] = aval(tuple(ps), s.dtype)
    step = _chunk_jit(model, mesh, "plain", paged=(bt, num_blocks))
    vec_i = aval((batch,), jnp.int32)
    vec_f = aval((batch,), jnp.float32)
    scalar = aval((), jnp.int32)
    key = aval(jax.random.PRNGKey(0).shape, jnp.uint32)
    seen = aval((batch, model.params.vocab_size), jnp.float32)
    table = aval((batch, seq_blocks), jnp.int32)
    carry = (vec_i, aval(tuple(token_x.shape), token_x.dtype), pools, key,
             seen)
    fargs = (vec_i, vec_f, vec_f)
    args = (variables, vec_i, vec_f, vec_i, scalar, fargs, (), table, table,
            carry)
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    context = {
        # q + token_x + key + seen ride the donated carry next to the pools
        "donated_leaves": len(pools) + 4,
        "protected": hlo_lint.shape_strings(pools, key_filter="/kv"),
        "cache_shapes": pools,
        "bf16_params": hlo_lint.shape_strings(variables, min_rank=2,
                                              dtypes={"bf16"}),
        "compiled": compiled,
        "trace": lambda: step.trace(*args).jaxpr,
    }
    return hlo, context


def lower_spec_step(model, variables, token_x, draft_model=None,
                    draft_variables=None, mesh=None):
    """Compiled donated SPECULATIVE chunk step (``infer/engine.py``
    ``_chunk_jit`` phase ``plain`` with a draft model — k+1 draft steps +
    one width-(k+1) verify in a single program): the donated carry holds BOTH cache pools
    — the target's slot pool AND the quarter-width draft's — and the audit
    pins every leaf of both aliased input->output with no full-pool-shaped
    copy.  The verify's sampled-token readback is the only fresh output.

    ``draft_model``/``draft_variables`` default to a fresh
    ``DRAFT_AUDIT_OVERRIDES`` build; abstract avals throughout, same
    OOM-safety argument as ``lower_decode_step``.
    """
    import jax
    import jax.numpy as jnp

    from ..infer.engine import _chunk_jit
    from ..infer.sampler import decode_cache_shapes

    if draft_model is None:
        _, draft_model, draft_variables, _, _ = build_audit_model(
            DRAFT_AUDIT_OVERRIDES, seed=1)
    aval = jax.ShapeDtypeStruct
    batch = token_x.shape[0]
    tps = token_x.shape[2]
    tshapes = decode_cache_shapes(model, variables, token_x)
    dshapes = decode_cache_shapes(draft_model, draft_variables, token_x)
    caches = {k: aval(v.shape, v.dtype) for k, v in tshapes.items()}
    dcaches = {k: aval(v.shape, v.dtype) for k, v in dshapes.items()}
    step = _chunk_jit(model, mesh, "plain", draft_model=draft_model,
                      k=model.params.spec_draft_tokens)
    vec_i = aval((batch,), jnp.int32)
    vec_f = aval((batch,), jnp.float32)
    vec_b = aval((batch,), jnp.bool_)
    key = aval(jax.random.PRNGKey(0).shape, jnp.uint32)
    seen = aval((batch, model.params.vocab_size), jnp.float32)
    carry = (aval(tuple(token_x.shape), token_x.dtype), caches, dcaches,
             key, seen)
    fargs = (vec_i, vec_f, vec_f)
    args = (variables, draft_variables, vec_i, vec_i, vec_f, vec_i, fargs,
            vec_b, aval((batch, tps), jnp.int32), vec_b, vec_i, (), carry)
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    context = {
        # token_x + key + seen ride the donated carry next to the two pools
        "donated_leaves": len(tshapes) + len(dshapes) + 3,
        "protected": (hlo_lint.shape_strings(tshapes, key_filter="/kv")
                      | hlo_lint.shape_strings(dshapes, key_filter="/kv")),
        # the two pools share cache key names (same scope paths at two
        # widths): namespace the draft's for consumers that need a flat map
        "cache_shapes": {**tshapes,
                         **{"draft/" + k: v for k, v in dshapes.items()}},
        "bf16_params": (hlo_lint.shape_strings(variables, min_rank=2,
                                               dtypes={"bf16"})
                        | hlo_lint.shape_strings(draft_variables, min_rank=2,
                                                 dtypes={"bf16"})),
        "compiled": compiled,
        "trace": lambda: step.trace(*args).jaxpr,
    }
    return hlo, context


def lower_spec_paged_step(model, variables, token_x, draft_model=None,
                          draft_variables=None, mesh=None):
    """Compiled donated SPEC-ON-PAGED chunk step — the composed program
    (``infer/engine.py`` ``ENGINE_PROGRAMS["spec_paged_chunk_step"]``):
    draft + width-(k+1) verify running over BLOCK POOLS for BOTH models,
    gathered/scattered through the same read/write tables.  The donated
    carry holds both pools at block geometry plus token_x/key/seen; the
    audit pins every leaf of both pools aliased input->output with no
    full-pool-shaped copy — composing the components must not cost a
    resident duplicate of either pool.

    Abstract avals throughout, same OOM-safety argument as
    ``lower_decode_step``."""
    import jax
    import jax.numpy as jnp

    from ..infer.engine import _chunk_jit
    from ..infer.paged import classify_cache_leaves
    from ..infer.sampler import decode_cache_shapes

    if draft_model is None:
        _, draft_model, draft_variables, _, _ = build_audit_model(
            DRAFT_AUDIT_OVERRIDES, seed=1)
    aval = jax.ShapeDtypeStruct
    batch, seq = token_x.shape[0], token_x.shape[1]
    tps = token_x.shape[2]
    bt = PAGED_AUDIT_BLOCK_TOKENS if seq % PAGED_AUDIT_BLOCK_TOKENS == 0 \
        else 1
    seq_blocks = seq // bt
    num_blocks = batch * seq_blocks

    def block_pools(shapes):
        info = classify_cache_leaves(shapes, seq)
        pools = {}
        for n, s in shapes.items():
            baxis, sax = info[n]
            if sax is None:
                pools[n] = aval(tuple(s.shape), s.dtype)
            else:
                ps = list(s.shape)
                ps[baxis], ps[sax] = num_blocks, bt
                pools[n] = aval(tuple(ps), s.dtype)
        return pools

    tshapes = decode_cache_shapes(model, variables, token_x)
    dshapes = decode_cache_shapes(draft_model, draft_variables, token_x)
    tpools = block_pools(tshapes)
    dpools = block_pools(dshapes)
    step = _chunk_jit(model, mesh, "plain", draft_model=draft_model,
                      k=model.params.spec_draft_tokens,
                      paged=(bt, num_blocks))
    vec_i = aval((batch,), jnp.int32)
    vec_f = aval((batch,), jnp.float32)
    vec_b = aval((batch,), jnp.bool_)
    key = aval(jax.random.PRNGKey(0).shape, jnp.uint32)
    seen = aval((batch, model.params.vocab_size), jnp.float32)
    table = aval((batch, seq_blocks), jnp.int32)
    carry = (aval(tuple(token_x.shape), token_x.dtype), tpools, dpools,
             key, seen)
    fargs = (vec_i, vec_f, vec_f)
    args = (variables, draft_variables, vec_i, vec_i, vec_f, vec_i, fargs,
            vec_b, aval((batch, tps), jnp.int32), vec_b, vec_i, (), table,
            table, carry)
    compiled = step.lower(*args).compile()
    hlo = compiled.as_text()
    context = {
        # token_x + key + seen ride the donated carry next to the two pools
        "donated_leaves": len(tpools) + len(dpools) + 3,
        "protected": (hlo_lint.shape_strings(tpools, key_filter="/kv")
                      | hlo_lint.shape_strings(dpools, key_filter="/kv")),
        "cache_shapes": {**tpools,
                         **{"draft/" + k: v for k, v in dpools.items()}},
        "bf16_params": (hlo_lint.shape_strings(variables, min_rank=2,
                                               dtypes={"bf16"})
                        | hlo_lint.shape_strings(draft_variables, min_rank=2,
                                                 dtypes={"bf16"})),
        "compiled": compiled,
        "trace": lambda: step.trace(*args).jaxpr,
    }
    return hlo, context


def _filter_args(batch: int, logits_filter: bool):
    import jax
    import jax.numpy as jnp
    aval = jax.ShapeDtypeStruct
    if not logits_filter:
        return ()
    return (aval((batch,), jnp.int32), aval((batch,), jnp.float32),
            aval((batch,), jnp.float32))


# ---- one-call audit --------------------------------------------------------

def lower_all(overrides: typing.Optional[dict] = None
              ) -> "typing.Dict[str, typing.Tuple[str, dict]]":
    """``{entry: (hlo_text, context)}`` for every registered entry point,
    from ONE shared audit model + trainer build.  Contexts carry the
    ``compiled`` executable (for ``cost_analysis``) and a ``trace`` thunk
    producing the entry's jaxpr — the cost ledger (analysis/cost_ledger.py)
    and the HLO audits below consume the same compiles, so running both in
    ``graft_lint --hlo`` pays the four compiles once."""
    import jax.numpy as jnp

    params, model, variables, token_x, batch = build_audit_model(overrides)
    trainer, state = make_trainer(params, model, batch)
    out: typing.Dict[str, typing.Tuple[str, dict]] = {}
    out["train_step"] = lower_train_step(params, model, variables, batch,
                                         trainer=trainer, state=state)
    out["decode_chunk_step"] = lower_decode_step(model, variables,
                                                 jnp.asarray(token_x))
    out["prefill_entry_step"] = lower_prefill_entry(model, variables,
                                                    jnp.asarray(token_x))
    out["eval_fn"] = lower_eval_fn(params, model, variables, batch,
                                   trainer=trainer, state=state)
    out["engine_chunk_step"] = lower_engine_step(model, variables,
                                                 jnp.asarray(token_x))
    out["paged_chunk_step"] = lower_paged_step(model, variables,
                                               jnp.asarray(token_x))
    draft_overrides = dict(overrides or {})
    draft_overrides.update(DRAFT_AUDIT_OVERRIDES)
    _, dmodel, dvariables, _, _ = build_audit_model(draft_overrides, seed=1)
    out["spec_chunk_step"] = lower_spec_step(model, variables,
                                             jnp.asarray(token_x),
                                             draft_model=dmodel,
                                             draft_variables=dvariables)
    out["spec_paged_chunk_step"] = lower_spec_paged_step(
        model, variables, jnp.asarray(token_x), draft_model=dmodel,
        draft_variables=dvariables)
    return out


def audit_lowered(lowered: "typing.Dict[str, typing.Tuple[str, dict]]",
                  budgets: typing.Optional[dict] = None
                  ) -> typing.List[hlo_lint.Finding]:
    """Every HLO pass over pre-lowered entry points (``lower_all``).
    Donation audit covers all four (eval's expectation is zero — a donation
    appearing there would be a bug of its own kind, but zero aliases is its
    honest baseline); the dtype-promotion pass skips the train step, where
    the optimizer's f32 slice dtype legitimately promotes param-shaped
    grads."""
    budgets = budgets if budgets is not None else hlo_lint.load_budgets()
    per_entry = budgets.get("entry_points", {})
    findings: typing.List[hlo_lint.Finding] = []

    hlo, ctx = lowered["train_step"]
    train_budget = per_entry.get("train_step", {})
    findings += hlo_lint.audit(
        "train_step", hlo,
        expected_aliases=ctx["donated_leaves"],
        protected_shapes=ctx["protected"],
        max_copied_bytes=int(train_budget.get("copy_byte_fraction", 0.0)
                             * ctx["donated_bytes"]),
        budget=train_budget)

    for entry in ("decode_chunk_step", "prefill_entry_step",
                  "engine_chunk_step", "spec_chunk_step",
                  "paged_chunk_step", "spec_paged_chunk_step"):
        hlo, ctx = lowered[entry]
        findings += hlo_lint.audit(
            entry, hlo,
            expected_aliases=ctx["donated_leaves"],
            protected_shapes=ctx["protected"],
            bf16_param_shapes=ctx["bf16_params"],
            budget=per_entry.get(entry, {}))

    hlo, ctx = lowered["eval_fn"]
    findings += hlo_lint.audit(
        "eval_fn", hlo,
        expected_aliases=ctx["donated_leaves"],
        bf16_param_shapes=ctx["bf16_params"],
        budget=per_entry.get("eval_fn", {}))

    return findings


def audit_all(overrides: typing.Optional[dict] = None,
              budgets: typing.Optional[dict] = None
              ) -> typing.List[hlo_lint.Finding]:
    """``audit_lowered(lower_all(overrides))`` — the one-call form tier-1
    and older callers use."""
    return audit_lowered(lower_all(overrides), budgets)
