"""Weight-only int8 serving quantization (core/quant.py).

Batch-1 decode is weight-read bound; int8 weights halve the bytes.  The
contract tested here: eligible weights round-trip within per-tensor int8
error (teacher-forcing loss moves by a small fraction), and the KV-cached
decode and full-forward sampler agree EXACTLY under the same quantized
weights — quantization must not break the cache machinery's internal
consistency even where it shifts the sampled tokens vs full precision.
"""
import jax.numpy as jnp
import numpy as np

from backend import MIXER_BLOCKS, make_params
from homebrewnlp_tpu.core.quant import quantize_variables
from homebrewnlp_tpu.infer.interface import InterfaceWrapper
from homebrewnlp_tpu.infer.sampler import sample_text
from homebrewnlp_tpu.model import Model


def _built(**kw):
    cfg = dict(features_per_head=128, heads=2, depth=2, train_batch_size=2,
               sequence_length=16, vocab_size=64,
               use_autoregressive_sampling=True,
               initial_autoregressive_position=4)
    cfg.update(kw)
    params = make_params(**cfg)
    params.train = False
    model = Model(params)
    rng = np.random.default_rng(0)
    x = rng.integers(0, params.vocab_size,
                     (params.train_batch_size, 16, 1)).astype(np.int32)
    batch = {"token_x": x, "token_y": x.copy()}
    variables = {k: jnp.asarray(v) for k, v in model.init(batch).items()}
    return params, model, variables, batch


def quantize_variables_selects_matmul_weights_test():
    params, model, variables, _ = _built()
    qvars, scales = quantize_variables(variables, model.param_dims,
                                       model.param_fan_in)
    assert set(qvars) == set(variables)
    quantized = [k for k, v in qvars.items() if v.dtype == jnp.int8]
    assert quantized, "no weight was quantized"
    assert set(quantized) == set(scales)
    multi_channel = 0
    for k in quantized:
        assert "embed" not in k
        assert np.size(variables[k]) >= 1 << 16
        w = np.asarray(variables[k], np.float32)
        s = np.asarray(scales[k], np.float32)
        # per-channel scales: each axis is either fully covered (a
        # non-contracted axis the consuming einsum keeps) or reduced to 1
        # (a contracted axis — a channel scale there could not commute out
        # of the sum); scales must stay a small fraction of the weight
        assert s.ndim == w.ndim
        assert all(a in (1, b) for a, b in zip(s.shape, w.shape)), \
            (s.shape, w.shape)
        assert s.size * 4 <= w.size  # f32 scales <= 1/4 of the int8 bytes
        multi_channel += sum(a > 1 for a in s.shape) > 1
        # round-trip error bounded by half a quantization step per channel
        back = np.asarray(qvars[k], np.float32) * s
        assert np.all(np.abs(w - back) <= s * 0.5 + 1e-7)
    # the fan-in record makes at least some weights carry scales over more
    # than one non-contracted axis (e.g. new = (heads, features_per_head))
    assert multi_channel, "fan-in-aware scales never went beyond last-axis"
    small = [k for k, v in qvars.items() if v.dtype != jnp.int8]
    assert small, "everything was quantized (norm/small vars should stay)"


def quantized_forward_loss_close_test():
    """Teacher-forcing loss under int8 weights stays within a small
    fraction of the full-precision loss (the quantization is usable, not
    just mechanically wired)."""
    params, model, variables, batch = _built()
    full = float(model.apply(variables, batch).total_loss.data)
    qvars, scales = quantize_variables(variables, model.param_dims,
                                       model.param_fan_in)
    model.quant_scales = scales
    try:
        quant = float(model.apply(qvars, batch).total_loss.data)
    finally:
        model.quant_scales = None
    assert abs(quant - full) / abs(full) < 0.02, (full, quant)


def quantized_scale_reaches_replayed_blocks_test():
    """The dequantize scale must be load-bearing on every path — including
    the scan/decode ReplayBlock contexts, which build fresh scope Contexts
    and must inherit ``quant_scales``.  Zeroing the scales must change the
    loss dramatically; if the plumbing dropped them, both runs would
    consume the same raw int8 values and agree (this architecture's norms
    make a silently-dropped per-tensor scale nearly invisible to the loss,
    so the loss-parity test alone cannot catch it)."""
    params, model, variables, batch = _built(depth=2, scan_layers=True)
    qvars, scales = quantize_variables(variables, model.param_dims,
                                       model.param_fan_in)
    model.quant_scales = scales
    try:
        with_scale = float(model.apply(qvars, batch).total_loss.data)
        model.quant_scales = {k: jnp.zeros_like(v) for k, v in scales.items()}
        zeroed = float(model.apply(qvars, batch).total_loss.data)
    finally:
        model.quant_scales = None
    assert abs(with_scale - zeroed) > 1e-3, \
        "zeroing the quant scales changed nothing — scales are being dropped"


def quantized_scan_unrolled_equivalence_test():
    """Scan-over-layers resolves every depth's params under the depth-0
    canonical names, so scales must be depth-shared (joint amax): the
    quantized model's loss must be IDENTICAL under scan_layers True/False.
    Before the shared-scale fix, scan silently applied depth-0's channel
    pattern to every depth while unrolled used per-depth scales — the two
    paths disagreed (a per-depth corruption test alone cannot see it
    because the scan never reads depth>0 scale entries at all)."""
    losses = {}
    for scan in (True, False):
        params, model, variables, batch = _built(depth=4, scan_layers=scan)
        qvars, scales = quantize_variables(variables, model.param_dims,
                                           model.param_fan_in)
        # sibling depths share one scale object, and the canonical name
        # (what the scan replay looks up) is present
        import re
        canon_keys = [k for k in scales if "block0_" in k]
        assert canon_keys
        deeper = [k for k in scales if re.search(r"block[1-9]", k)]
        assert deeper, "depth>0 scale entries missing"
        for k in deeper:
            c = re.sub(r"block\d+_", "block0_", k)
            assert scales[c] is scales[k], (k, "scale not depth-shared")
        model.quant_scales = scales
        try:
            losses[scan] = float(model.apply(qvars, batch).total_loss.data)
        finally:
            model.quant_scales = None
    assert losses[True] == losses[False], losses


def stale_scales_ignore_full_precision_weights_test():
    """A Model whose quant_scales were set by a quantized wrapper must
    apply cleanly to FULL-PRECISION variables: the dtype gate in
    materialize_param scales only int8 data."""
    params, model, variables, batch = _built()
    full = float(model.apply(variables, batch).total_loss.data)
    _, scales = quantize_variables(variables, model.param_dims,
                                       model.param_fan_in)
    model.quant_scales = scales  # stale: variables below are NOT quantized
    try:
        again = float(model.apply(variables, batch).total_loss.data)
    finally:
        model.quant_scales = None
    assert again == full, (full, again)


def quantized_decode_internal_consistency_test():
    """Under the SAME quantized weights, the KV-cached sampler and the
    full-forward sampler produce identical greedy tokens — the cache
    machinery sees quantized layers transparently."""
    params, model, variables, batch = _built()
    qvars, scales = quantize_variables(variables, model.param_dims,
                                       model.param_fan_in)
    model.quant_scales = scales
    try:
        prompt = np.asarray(batch["token_x"])[:, :4, 0]
        cached = sample_text(model, qvars, prompt, initial_pos=4,
                             temperature=0.0, use_cache=True)
        full = sample_text(model, qvars, prompt, initial_pos=4,
                           temperature=0.0, use_cache=False)
    finally:
        model.quant_scales = None
    np.testing.assert_array_equal(cached, full)


def quantized_sharded_decode_parity_test():
    """int8 weights under a dp x tp mesh: sharded greedy decode equals the
    single-device quantized decode exactly (the int8 arrays + their scales
    ride the same NamedSharding machinery as full-precision weights)."""
    import jax
    import pytest
    from homebrewnlp_tpu.core import sharding as shardlib
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    params, model, variables, batch = _built(
        heads=4, train_batch_size=4,
        mesh_shape_override={"data": 2, "model": 4})
    qvars, scales = quantize_variables(variables, model.param_dims,
                                       model.param_fan_in)
    model.quant_scales = scales
    try:
        prompt = np.asarray(batch["token_x"])[:, :4, 0]
        single = sample_text(model, qvars, prompt, initial_pos=4,
                             temperature=0.0)
        mesh = shardlib.build_mesh(params)
        sharded_q = shardlib.shard_params(params, qvars, model.param_dims,
                                          mesh)
        assert any(v.dtype == jnp.int8 for v in sharded_q.values())
        out = sample_text(model, sharded_q, prompt, initial_pos=4,
                          temperature=0.0, mesh=mesh)
    finally:
        model.quant_scales = None
    np.testing.assert_array_equal(single, out)


def interface_serve_quantized_weights_test():
    """The config flag wires quantization through the serving interface:
    variables become int8 where eligible and completions run end-to-end."""
    params, model, variables, batch = _built(train_batch_size=1)
    params.serve_quantized_weights = True
    iface = InterfaceWrapper(params, model, variables)
    assert any(v.dtype == jnp.int8 for v in iface.variables.values())
    out = iface.complete_tokens(np.asarray([5, 6, 7], np.int32),
                                temperature=0.0)
    assert out.shape[0] == 16 // params.token_patch_size * \
        params.token_patch_size or out.size > 0
    assert (np.asarray(out) >= 0).all()
    assert (np.asarray(out) < params.vocab_size).all()
