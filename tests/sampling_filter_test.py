"""Top-k / nucleus (top-p) sampling filters (beyond-reference serving
surface: the reference samples the full distribution only,
/root/reference/src/run/inference.py:88-92)."""
import jax.numpy as jnp
import numpy as np

from backend import make_params
from homebrewnlp_tpu.infer.sampler import _filter_logits, sample_text
from homebrewnlp_tpu.model import Model

ATTN_BLOCKS = [{"layer": ["norm-shift-scale-features-group",
                          "attention-dot_product-context-in:relu"]}]


def filter_logits_masks_test():
    """Unit semantics on raw logits: top-k keeps exactly the k largest,
    top-p keeps the smallest prefix of the sorted distribution with mass
    >= p, disabled values are identity."""
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.standard_normal((2, 1, 1, 16)).astype(np.float32))
    tb = jnp.asarray([1.0, 1.0], jnp.float32)

    # disabled -> identity
    out = _filter_logits(logits, tb, jnp.asarray([0, 0], jnp.int32),
                         jnp.asarray([1.0, 1.0], jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))

    # top-k=3 keeps exactly the 3 largest per row
    out = np.asarray(_filter_logits(logits, tb,
                                    jnp.asarray([3, 3], jnp.int32),
                                    jnp.asarray([1.0, 1.0], jnp.float32)))
    for b in range(2):
        row = np.asarray(logits)[b, 0, 0]
        kept = out[b, 0, 0] > -1e29
        assert kept.sum() == 3
        assert set(np.flatnonzero(kept)) == set(np.argsort(row)[-3:])

    # top-p: kept set is the minimal sorted prefix with mass >= p
    p = 0.5
    out = np.asarray(_filter_logits(logits, tb,
                                    jnp.asarray([0, 0], jnp.int32),
                                    jnp.asarray([p, p], jnp.float32)))
    for b in range(2):
        row = np.asarray(logits)[b, 0, 0]
        probs = np.exp(row - row.max())
        probs /= probs.sum()
        order = np.argsort(-row)
        cum = np.cumsum(probs[order])
        n_expect = int(np.searchsorted(cum, p)) + 1
        kept = np.flatnonzero(out[b, 0, 0] > -1e29)
        assert set(kept) == set(order[:n_expect]), (kept, order[:n_expect])

    # per-row: row 0 filtered to k=1, row 1 untouched
    out = np.asarray(_filter_logits(logits, tb,
                                    jnp.asarray([1, 0], jnp.int32),
                                    jnp.asarray([1.0, 1.0], jnp.float32)))
    assert (out[0, 0, 0] > -1e29).sum() == 1
    np.testing.assert_array_equal(out[1], np.asarray(logits)[1])


def filter_temperature_scaling_test():
    """Nucleus mass is computed on softmax(logits / T) — hotter rows spread
    mass, so the same top_p keeps MORE tokens."""
    rng = np.random.default_rng(1)
    logits = jnp.asarray(np.repeat(
        rng.standard_normal((1, 1, 1, 32)).astype(np.float32), 2, axis=0))
    out = np.asarray(_filter_logits(
        logits, jnp.asarray([0.3, 3.0], jnp.float32),
        jnp.asarray([0, 0], jnp.int32), jnp.asarray([0.7, 0.7], jnp.float32)))
    cold = (out[0, 0, 0] > -1e29).sum()
    hot = (out[1, 0, 0] > -1e29).sum()
    assert cold < hot, (cold, hot)


def _tiny_model(seed=0):
    params = make_params(block_config=ATTN_BLOCKS,
                         memory_reduction_strategy="none",
                         sequence_length=16, depth=2, heads=2,
                         features_per_head=8, train_batch_size=2,
                         vocab_size=32, use_autoregressive_sampling=True)
    model = Model(params)
    rng = np.random.default_rng(seed)
    token_x = rng.integers(0, params.vocab_size,
                           (2, 16, 1)).astype(np.int32)
    batch = {"token_x": jnp.asarray(token_x), "token_y": jnp.asarray(token_x)}
    variables = {k: jnp.asarray(v) for k, v in model.init(batch).items()}
    return model, variables, token_x


def top_k1_is_greedy_test():
    """top_k=1 at high temperature must reproduce the greedy stream —
    the strongest end-to-end check that the mask reaches the loop."""
    model, variables, token_x = _tiny_model()
    prompt = token_x[:, :4, 0]
    greedy = sample_text(model, variables, prompt, initial_pos=4,
                         temperature=0.0, seed=7)
    topk1 = sample_text(model, variables, prompt, initial_pos=4,
                        temperature=1.7, top_k=1, seed=7)
    np.testing.assert_array_equal(greedy, topk1)


def top_p_tiny_is_greedy_test():
    """top_p -> 0 keeps only the crossing (max) token: greedy stream."""
    model, variables, token_x = _tiny_model()
    prompt = token_x[:, :4, 0]
    greedy = sample_text(model, variables, prompt, initial_pos=4,
                         temperature=0.0, seed=3)
    nucleus = sample_text(model, variables, prompt, initial_pos=4,
                          temperature=1.3, top_p=1e-6, seed=3)
    np.testing.assert_array_equal(greedy, nucleus)


def disabled_filters_match_plain_path_test():
    """top_k=0 / top_p=1.0 route through the plain (unfiltered) jit kind:
    same tokens as a call that never mentions the filters."""
    model, variables, token_x = _tiny_model()
    prompt = token_x[:, :4, 0]
    plain = sample_text(model, variables, prompt, initial_pos=4,
                        temperature=0.9, seed=11)
    disabled = sample_text(model, variables, prompt, initial_pos=4,
                           temperature=0.9, top_k=0, top_p=1.0, seed=11)
    np.testing.assert_array_equal(plain, disabled)


def per_row_filters_test():
    """Row 0 with top_k=1 must be greedy while row 1 stays stochastic —
    per-request filters in one batched decode call (serving)."""
    model, variables, token_x = _tiny_model()
    prompt = token_x[:, :4, 0]
    greedy = sample_text(model, variables, prompt, initial_pos=4,
                         temperature=0.0, seed=5)
    mixed = sample_text(model, variables, prompt, initial_pos=4,
                        temperature=1.7, top_k=np.asarray([1, 0], np.int32),
                        seed=5)
    np.testing.assert_array_equal(mixed[0], greedy[0])
    assert not np.array_equal(mixed[1], greedy[1])


def top_p_zero_is_greedy_test():
    """top_p=0 (a common client idiom) must be maximally restrictive —
    exactly the argmax survives — not silently disabled (the nkeep clamp)."""
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.standard_normal((2, 1, 1, 16)).astype(np.float32))
    out = np.asarray(_filter_logits(
        logits, jnp.asarray([1.0, 1.0], jnp.float32),
        jnp.asarray([0, 0], jnp.int32), jnp.asarray([0.0, 0.0], jnp.float32)))
    for b in range(2):
        kept = np.flatnonzero(out[b, 0, 0] > -1e29)
        assert list(kept) == [int(np.argmax(np.asarray(logits)[b, 0, 0]))]


def top_k_then_top_p_renormalizes_test():
    """Sequential warper order (HF): the nucleus mass renormalizes over the
    top-k survivors, so top_p can drop low-probability members OF the
    top-k set."""
    # 4 tokens: probs ~ [0.4, 0.3, 0.2, 0.1] at T=1
    base = np.log(np.asarray([0.4, 0.3, 0.2, 0.1], np.float32))
    logits = jnp.asarray(base[None, None, None, :])
    tb = jnp.asarray([1.0], jnp.float32)
    # top_k=3 keeps {0,1,2} with renormalized probs [4/9, 3/9, 2/9];
    # top_p=0.8: prefix mass before token2 = 7/9 = 0.778 < 0.8 -> token2
    # kept; check against top_p=0.7: 0.778 > 0.7 -> token2 dropped
    out_hi = np.asarray(_filter_logits(logits, tb,
                                       jnp.asarray([3], jnp.int32),
                                       jnp.asarray([0.8], jnp.float32)))
    out_lo = np.asarray(_filter_logits(logits, tb,
                                       jnp.asarray([3], jnp.int32),
                                       jnp.asarray([0.7], jnp.float32)))
    assert set(np.flatnonzero(out_hi[0, 0, 0] > -1e29)) == {0, 1, 2}
    assert set(np.flatnonzero(out_lo[0, 0, 0] > -1e29)) == {0, 1}


def batched_serving_uses_config_defaults_test():
    """complete_tokens_batch rows without explicit filters inherit the
    sampling_top_k config default (the operator's serving config must bind
    on the batched path, not only the single-request one)."""
    from homebrewnlp_tpu.infer.interface import InterfaceWrapper
    model, variables, token_x = _tiny_model()
    model.params.sampling_top_k = 1   # serving default: greedy-equivalent
    try:
        iface = InterfaceWrapper.__new__(InterfaceWrapper)
        iface.params = model.params
        iface.model = model
        iface.variables = variables
        iface.mesh = None
        iface.decode_calls = 0
        iface._model_for_width = lambda w: (None, model)
        prompt = [token_x[0, :4, 0], token_x[1, :4, 0]]
        outs = iface.complete_tokens_batch(prompt, temperatures=[1.7, 1.7],
                                           seed=9)
        greedy = sample_text(model, variables, np.stack(prompt),
                             initial_pos=4, temperature=0.0, seed=9)
        for i in range(2):
            np.testing.assert_array_equal(outs[i][4:],
                                          greedy[i, 4:len(outs[i]), 0])
    finally:
        model.params.sampling_top_k = 0


def repetition_penalty_unit_test():
    """HF semantics: seen tokens' positive logits divide by r, negative
    multiply by r; unseen unchanged; r=1 identity."""
    from homebrewnlp_tpu.infer.sampler import _repetition_penalty
    logits = jnp.asarray([[[[2.0, -2.0, 1.0, -1.0]]]], jnp.float32)
    seen = jnp.asarray([[1.0, 1.0, 0.0, 0.0]], jnp.float32)
    out = np.asarray(_repetition_penalty(
        logits, seen, jnp.asarray([2.0], jnp.float32)))[0, 0, 0]
    np.testing.assert_allclose(out, [1.0, -4.0, 1.0, -1.0])
    out1 = np.asarray(_repetition_penalty(
        logits, seen, jnp.asarray([1.0], jnp.float32)))
    np.testing.assert_array_equal(out1, np.asarray(logits))


def repetition_penalty_kv_full_parity_test():
    """Greedy decode with a strong penalty: the KV sampler (carry-updated
    seen counts) and the full-forward sampler (recomputed per step) are
    independent implementations and must produce identical streams."""
    model, variables, token_x = _tiny_model()
    prompt = token_x[:, :4, 0]
    kw = dict(initial_pos=4, temperature=0.0, repetition_penalty=4.0, seed=2)
    kv = sample_text(model, variables, prompt, use_cache=True, **kw)
    full = sample_text(model, variables, prompt, use_cache=False, **kw)
    np.testing.assert_array_equal(kv, full)
    # and the penalty actually changes the greedy stream (untrained tiny
    # models repeat; a x4 penalty must break the loop)
    plain = sample_text(model, variables, prompt, initial_pos=4,
                        temperature=0.0, seed=2)
    assert not np.array_equal(kv, plain)


def repetition_penalty_empty_prompt_parity_test():
    """initial_pos=0 (empty prompt): the zero_first token at index 0 must be
    counted as seen by BOTH samplers — the kv/full parity edge the prompt
    seeding could miss."""
    model, variables, token_x = _tiny_model()
    prompt = token_x[:, :1, 0] * 0
    kw = dict(initial_pos=0, temperature=0.0, repetition_penalty=4.0, seed=6)
    kv = sample_text(model, variables, prompt, use_cache=True, **kw)
    full = sample_text(model, variables, prompt, use_cache=False, **kw)
    np.testing.assert_array_equal(kv, full)
