"""In-place KV-cache decode carry (ISSUE 2 tentpole).

Three properties of the stepped (donated chunked) decode loop:

  1. exact greedy/filtered parity with the fused while_loop sampler and the
     full-forward reference sampler — the loop restructure must not change
     one sampled token;
  2. the COMPILED per-token step contains no full-KV-cache-shaped copy and
     aliases every donated cache leaf input->output (analysis/hlo_lint.py) —
     the property whose loss made 32k decode cost 7.5x its read bound
     (BASELINE.md round 5); this asserts the fix at the artifact level, not
     the source level;
  3. the sequence-scaling probe is ~linear in cache bytes (slow-marked:
     timing-based).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from backend import MIXER_BLOCKS, make_params
from homebrewnlp_tpu.analysis import entry_points, hlo_lint
from homebrewnlp_tpu.infer.sampler import (_sample_kv_stepped,
                                           decode_cache_bytes,
                                           init_decode_caches,
                                           make_kv_sampler, make_sampler)
from homebrewnlp_tpu.model import Model


def _build(cfg_overrides, seed=0):
    params = make_params(**cfg_overrides)
    model = Model(params)
    rng = np.random.default_rng(seed)
    seq = params.sequence_dim.size
    tps = params.token_patch_dim.size
    token_x = rng.integers(0, params.vocab_size,
                           (params.train_batch_size, seq, tps)
                           ).astype(np.int32)
    batch = {"token_x": jnp.asarray(token_x), "token_y": jnp.asarray(token_x)}
    variables = {k: jnp.asarray(v) for k, v in model.init(batch).items()}
    return params, model, variables, token_x


def stepped_decode_parity_test():
    """Greedy outputs of full-forward, fused-while_loop, and stepped
    samplers are identical — at 3x the harness default sequence and depth
    (a cache deep/long enough to exercise the restructured stacked carry)
    with a chunk size that forces many donated dispatches and a
    non-chunk-aligned final chunk."""
    params, model, variables, token_x = _build(
        {"block_config": MIXER_BLOCKS, "memory_reduction_strategy": "revnet",
         "sequence_length": 48, "depth": 3, "decode_chunk_tokens": 5})
    seq = params.sequence_dim.size
    full = jax.jit(make_sampler(model))(
        variables, jnp.asarray(token_x), jnp.asarray(token_x),
        jnp.int32(4), jnp.float32(0.0), jnp.int32(seq), jax.random.PRNGKey(0))
    caches = init_decode_caches(model, variables, jnp.asarray(token_x))
    fused = jax.jit(make_kv_sampler(model))(
        variables, jnp.asarray(token_x), jnp.int32(4), jnp.float32(0.0),
        jnp.int32(seq), jax.random.PRNGKey(0), caches)
    stepped = _sample_kv_stepped(model, variables, jnp.asarray(token_x),
                                 4, 0.0, seq, jax.random.PRNGKey(0))
    np.testing.assert_array_equal(np.asarray(full), np.asarray(fused))
    np.testing.assert_array_equal(np.asarray(full), np.asarray(stepped))


def stepped_prefill_parity_test():
    """The stepped loop entered after a one-shot prefill produces the same
    greedy stream as walking from position 0."""
    params, model, variables, token_x = _build(
        {"block_config": MIXER_BLOCKS, "memory_reduction_strategy": "none",
         "decode_chunk_tokens": 3})
    seq = params.sequence_dim.size
    walk = _sample_kv_stepped(model, variables, jnp.asarray(token_x),
                              6, 0.0, seq, jax.random.PRNGKey(0),
                              prefill=False)
    pf = _sample_kv_stepped(model, variables, jnp.asarray(token_x),
                            6, 0.0, seq, jax.random.PRNGKey(0), prefill=True)
    np.testing.assert_array_equal(np.asarray(walk), np.asarray(pf))


def stepped_filter_parity_test():
    """Sampled (temperature + top-k/top-p/repetition) streams match the
    fused sampler bit-for-bit: both loops consume the identical per-step
    gumbel draw through the identical body."""
    params, model, variables, token_x = _build(
        {"block_config": MIXER_BLOCKS, "memory_reduction_strategy": "none",
         "decode_chunk_tokens": 4})
    seq = params.sequence_dim.size
    batch = token_x.shape[0]
    fargs = (jnp.full((batch,), 5, jnp.int32),
             jnp.full((batch,), 0.9, jnp.float32),
             jnp.full((batch,), 1.3, jnp.float32))
    caches = init_decode_caches(model, variables, jnp.asarray(token_x))
    fused = jax.jit(make_kv_sampler(model, logits_filter=True))(
        variables, jnp.asarray(token_x), jnp.int32(4), jnp.float32(0.7),
        jnp.int32(seq), jax.random.PRNGKey(3), caches, *fargs)
    stepped = _sample_kv_stepped(model, variables, jnp.asarray(token_x),
                                 4, 0.7, seq, jax.random.PRNGKey(3),
                                 fargs=fargs)
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(stepped))


def sample_text_stepped_routing_test():
    """decode_loop="stepped" routes sample_text through the donated chunk
    step (observable via the per-model jit cache; the prompt region must
    come back intact), and flipping the same model's knobs exercises the
    "auto" threshold routing against the measured cache size.  Output
    parity between the loops is pinned by the parity tests above —
    re-deriving it here would pay a second fused compile for no new
    information."""
    from homebrewnlp_tpu.infer.sampler import (_use_stepped_loop,
                                               sample_text)
    _, model, variables, token_x = _build(
        {"block_config": MIXER_BLOCKS, "memory_reduction_strategy": "none",
         "decode_chunk_tokens": 4, "decode_loop": "stepped"})
    out = sample_text(model, variables, token_x[:, :4, 0],
                      initial_pos=4, temperature=0.0)
    assert any(k[1].startswith("kv_step")
               for k in model._sampler_jit_cache)
    np.testing.assert_array_equal(out[:, 1:4, 0], token_x[:, 1:4, 0])
    # "auto" picks the loop by measured cache size vs the threshold knob
    nbytes = decode_cache_bytes(model, variables, token_x)
    assert nbytes > 0
    model.params.decode_loop = "auto"
    model.params.decode_stepped_min_cache_gb = (nbytes + 1) / 1024 ** 3
    assert not _use_stepped_loop(model, variables, token_x)
    model.params.decode_stepped_min_cache_gb = (nbytes - 1) / 1024 ** 3
    assert _use_stepped_loop(model, variables, token_x)


def _assert_no_full_cache_copy(hlo_text, cache_shapes, min_aliases=None):
    """AssertionError if the compiled module contains a ``copy`` whose result
    is exactly a full KV-cache buffer (the aliaser inserts such copies when
    it cannot keep the carry update in place; the small recurrence caches
    legitimately rewrite their whole buffer and are left out), or if fewer
    than ``min_aliases`` input/output aliases were established.  Strict
    (``max_copied_bytes=0``): ANY full-cache copy of live state is the
    round-5 regression."""
    targets = hlo_lint.shape_strings(cache_shapes, key_filter="/kv")
    assert targets, f"no KV cache leaves in {list(cache_shapes)[:5]}"
    findings = hlo_lint.big_copy_audit("decode_chunk_step", hlo_text,
                                       targets, max_copied_bytes=0)
    if min_aliases is not None:
        findings = findings + hlo_lint.donation_audit(
            "decode_chunk_step", hlo_text, min_aliases)
    assert not findings, "\n".join(str(f) for f in findings)


def _assert_decode_step_inplace(model, variables, token_x,
                                logits_filter=False):
    """The per-token decode step's compiled module keeps every cache update
    in place (no full-cache copy, caches all aliased)."""
    hlo, ctx = entry_points.lower_decode_step(model, variables, token_x,
                                              logits_filter=logits_filter)
    _assert_no_full_cache_copy(hlo, ctx["cache_shapes"],
                               min_aliases=ctx["donated_leaves"])


def decode_step_inplace_hlo_test():
    """The compiled donated step: no full-cache-shaped copy, every cache
    leaf aliased input->output.  Revnet is the flagship strategy (the
    depth-scan layout); the "none" strategy rides the filter variant below
    and int8 its own test — together the three scan layouts and cache
    dtypes are covered at one compile each."""
    _, model, variables, token_x = _build(
        {"block_config": MIXER_BLOCKS, "memory_reduction_strategy": "revnet"})
    _assert_decode_step_inplace(model, variables, jnp.asarray(token_x))


def decode_step_int8_inplace_hlo_test():
    """int8 caches add the sibling f32 scale buffers to the donated carry;
    both must alias (a copied scale cache would silently re-grow with
    context length like the round-5 bug)."""
    _, model, variables, token_x = _build(
        {"block_config": MIXER_BLOCKS, "memory_reduction_strategy": "revnet",
         "decode_cache_dtype": "int8"})
    _assert_decode_step_inplace(model, variables, jnp.asarray(token_x))


def decode_step_filter_inplace_hlo_test():
    """The logits-filter variant (extra ``seen`` carry leaf) keeps the
    cache aliasing property."""
    _, model, variables, token_x = _build(
        {"block_config": MIXER_BLOCKS, "memory_reduction_strategy": "none"})
    _assert_decode_step_inplace(model, variables, jnp.asarray(token_x),
                                logits_filter=True)


def hlo_checker_detects_full_cache_copy_test():
    """Negative control: the checker FLAGS a module that copies a
    full-cache-shaped buffer, and passes the same module once the copy is
    block-shaped — so a future aliasing regression cannot slip through a
    vacuous assertion."""
    shapes = {"cache/x/kv0": jax.ShapeDtypeStruct((2, 4, 16, 2, 16),
                                                  jnp.float32)}
    bad = ("%copy.9 = f32[2,4,16,2,16]{4,3,2,1,0} "
           "copy(f32[2,4,16,2,16]{4,3,2,1,0} %get-tuple-element.1)")
    ok = ("%copy.9 = f32[4,16,2,16]{3,2,1,0} "
          "copy(f32[4,16,2,16]{2,0,3,1} %transpose.1)")
    with pytest.raises(AssertionError, match="NOT aliased"):
        _assert_no_full_cache_copy(bad, shapes)
    _assert_no_full_cache_copy(ok, shapes)
    assert hlo_lint.input_output_alias_count(
        "input_output_alias={ {0}: (31, {}, may-alias), "
        "{1}: (32, {}, may-alias) }") == 2


def spread_records_row_updates_test():
    """The KV scatter site records the row it wrote (and the int8 scale
    row) so the depth scan can copy back a ROW instead of the block —
    model/blocks.py relies on the recording to keep per-token writes
    row-sized."""
    from homebrewnlp_tpu.core import scope as scope_mod
    from homebrewnlp_tpu.core.dims import Dim
    from homebrewnlp_tpu.core.tensor import nt as nt_
    from homebrewnlp_tpu.model.decode import DecodeState, spread
    rng = np.random.default_rng(0)
    b, h, f, s = 2, 3, 8, 8
    x = jnp.asarray(rng.standard_normal((b, 1, h, f)), jnp.float32)
    dims = [Dim("batch", b), Dim("sequence", 1), Dim("heads", h),
            Dim("features_per_head", f)]
    for dtype, n_updates in ((None, 1), (jnp.int8, 2)):
        state = DecodeState(jnp.int32(2), s, "sequence", {},
                            cache_dtype=dtype)
        ctx = scope_mod.Context("apply", params={})
        ctx.decode = state
        with scope_mod.context(ctx):
            spread(nt_(x, dims), dims[1])
        assert len(state.row_updates) == n_updates, state.row_updates
        for name, (row, axis) in state.row_updates.items():
            assert axis == 1, (name, axis)
            assert row.shape[axis] == 1, (name, row.shape)
            assert row.shape[0] == b


def rest_health_decode_path_test():
    """/health reports which decode loop serves the deployment (the ops
    surface for the in-place carry property)."""
    from homebrewnlp_tpu.infer.interface import InterfaceWrapper
    from homebrewnlp_tpu.infer.rest_api import _handlers
    params, model, variables, _ = _build(
        {"block_config": MIXER_BLOCKS, "memory_reduction_strategy": "none",
         "decode_loop": "stepped"})
    iface = InterfaceWrapper(params, model, variables)
    res = _handlers(iface)["/health"]({})
    assert res["status"] == "ok"
    assert res["decode_path"]["loop"] == "stepped"
    assert res["decode_path"]["cache_gb"] >= 0
    assert res["decode_path"]["chunk_tokens"] == params.decode_chunk_tokens
