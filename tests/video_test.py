"""Video (jannet) mode tests: full model fwd/bwd with frames+tokens+masks,
multi-axis attention cycling, video pipeline decode/window semantics."""
import os

import jax
import jax.numpy as jnp
import numpy as np

from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.model import Model


def _video_params(**overrides):
    cfg = {
        "model_mode": "jannet", "use_video": True, "use_language": True,
        "sequence_length": 4, "time_patch": 1, "patch_size": 4,
        "frame_height": 8, "frame_width": 8, "color_channels": 3,
        "language_token_per_frame": 4, "token_patch_size": 1,
        "features_per_head": 8, "heads": 2, "depth": 1,
        "train_batch_size": 2, "vocab_size": 32, "experts": 1,
        "three_axes": False, "memory_reduction_strategy": "none",
        "calc_accuracy": False,
        "block_config": [
            {"layer": ["norm-shift-scale-features-group",
                       "attention-biased_attention_map-absolute-input_as_value"]}],
        "group_linear_factor": 2,
    }
    cfg.update(overrides)
    return ModelParameter(cfg)


def _video_batch(params, rng):
    p = params
    b, tps = p.train_batch_size, p.time_patch_size
    if p.three_axes:
        fshape = (b, tps + 1, p.frame_height_patch, p.frame_width_patch,
                  p.channel_color_size)
    else:
        fshape = (b, tps + 1, p.frame_height_patch * p.frame_width_patch,
                  p.channel_color_size)
    frame = rng.integers(0, 255, fshape).astype(np.int32)
    tokens = rng.integers(0, p.vocab_size,
                          (b, tps, p.language_token_patch, p.token_patch_size))
    return {
        "frame": jnp.asarray(frame),
        "token_x": jnp.asarray(tokens.astype(np.int32)),
        "token_y": jnp.asarray(tokens.astype(np.int32)),
        "cat_mask_x": jnp.ones((b, tps), jnp.float32),
        "cat_mask_y": jnp.ones((b, tps), jnp.float32),
        "vid_msk_src": jnp.ones((b, tps), jnp.float32),
        "vid_msk_tgt": jnp.ones((b, tps), jnp.float32),
        "txt_msk": jnp.ones((b, tps, p.language_token_patch,
                             p.token_patch_size), jnp.float32),
    }


def unpatchify_roundtrip_test():
    """render's inverse must exactly undo the input pipeline's patchify
    (data/video.py:60), including patch_size > 1."""
    params = _video_params(patch_size=4, frame_height=8, frame_width=16)
    hp, wp, ps, c = (params.frame_height_patch, params.frame_width_patch,
                     params.patch_size, params.color_channels)
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (params.frame_height, params.frame_width, c))
    packed = img.reshape(hp, ps, wp, ps, c).transpose(1, 3, 0, 2, 4)
    packed = packed.reshape(hp * wp, params.channel_color_size)
    from homebrewnlp_tpu.infer.interface import unpatchify
    restored = unpatchify(packed[None], params)[0]
    np.testing.assert_array_equal(restored, img)
    # three_axes view of the same memory unpatchifies identically
    restored3 = unpatchify(
        packed.reshape(hp, wp, params.channel_color_size)[None], params)[0]
    np.testing.assert_array_equal(restored3, img)


def video_sampling_and_render_test(tmp_path):
    """Autoregressive frame continuation + avi render (reference
    inference.py:25-73, interface.py:13-58)."""
    params = _video_params(initial_autoregressive_position=1,
                           use_autoregressive_sampling=True)
    m = Model(params)
    rng = np.random.default_rng(0)
    batch = _video_batch(params, rng)
    variables = {k: jnp.asarray(v) for k, v in m.init(batch).items()}
    from homebrewnlp_tpu.infer.sampler import sample_video
    frames01, tokens = sample_video(m, variables, batch, initial_pos=1)
    assert frames01.shape == batch["frame"].shape
    assert np.all(np.isfinite(frames01))
    assert 0.0 <= frames01[:, 1:].min() and frames01[:, 1:].max() <= 1.0
    assert tokens is not None and tokens.shape == batch["token_x"].shape
    # the sampled positions must differ from the prompt with overwhelming
    # probability (random init still produces non-trivial frame outputs)
    assert not np.allclose(frames01[:, 2], np.asarray(batch["frame"])[:, 2] / 255.0)
    from homebrewnlp_tpu.infer.interface import render_video
    out = render_video(frames01[0], ["hi"] * frames01.shape[1], params,
                       str(tmp_path / "clip"))
    import os
    assert os.path.exists(out) and os.path.getsize(out) > 0


def video_forward_backward_test():
    params = _video_params()
    m = Model(params)
    rng = np.random.default_rng(0)
    batch = _video_batch(params, rng)
    variables = m.init(batch)
    def loss_fn(v):
        info = m.apply(v, batch)
        return info.total_loss.data
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(variables)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.sum(g.astype(jnp.float32) ** 2)) for g in grads.values())
    assert np.isfinite(gnorm) and gnorm > 0


def video_loss_components_test():
    params = _video_params()
    m = Model(params)
    rng = np.random.default_rng(0)
    batch = _video_batch(params, rng)
    variables = m.init(batch)
    info = m.apply(variables, batch)
    assert info.video_loss is not None and np.isfinite(float(info.video_loss.data))
    assert info.token_loss is not None and np.isfinite(float(info.token_loss.data))
    # frame head output dims: [batch, seq, height(minus txt ctx), width, colors]
    assert info.frame_out is not None


def multi_axis_attention_cycles_test():
    """attention_idx round-robins over sequence/height/width for video
    (reference utils_mtf.py:418-422); pure-video mode has all three axes."""
    params = _video_params(depth=3, use_language=False, three_axes=True,
                           language_token_per_frame=0, frame_width=12,
                           experts=1)
    m = Model(params)
    rng = np.random.default_rng(0)
    batch = _video_batch(params, rng)
    variables = m.init(batch)
    # bias embeds must exist for three distinct mixing axes across depth
    bias_shapes = {tuple(v.shape) for k, v in variables.items()
                   if "attention" in k and "embed" in k}
    assert len(bias_shapes) == 3, bias_shapes


def bit_fold_pipeline_test():
    """bit-folded input unpacks to the same frames in the model _input
    (reference model/__init__.py:45-57, inputs.py:183-197)."""
    from homebrewnlp_tpu.data.video import decode_frame_record
    from homebrewnlp_tpu.data.tfrecord import encode_example
    import cv2
    params = _video_params(use_bit_fold_input_pipeline=True, bit_fold_value=8,
                           color_quantization_value=256)
    rng = np.random.default_rng(0)
    img = rng.integers(0, 255, (8, 8, 3)).astype(np.uint8)
    ok, enc = cv2.imencode(".png", img)
    assert ok
    payload = encode_example({"frame": enc.tobytes(), "concat": [0],
                              "skip_frame": [0]})
    frame, concat, skip, _, _ = decode_frame_record(params, payload, False)
    assert frame.dtype == np.uint32
    expect = (params.frame_height_patch, params.frame_width_patch,
              params.channel_color_size) if params.three_axes else \
        (params.frame_height_patch * params.frame_width_patch,
         params.channel_color_size)
    assert frame.shape == expect
    # unfold (model _input semantics) must reproduce the unfolded decode
    params2 = _video_params(use_bit_fold_input_pipeline=False)
    frame2, *_ = decode_frame_record(params2, payload, False)
    fold = 32 // params.bit_fold_value
    unpacked = np.stack([(frame >> (8 * i)) & 0xFF for i in range(fold)],
                        axis=-2).reshape(frame2.shape)
    np.testing.assert_array_equal(unpacked, frame2)


def video_dataset_test(tmp_path):
    from homebrewnlp_tpu.data.tfrecord import RecordWriter, encode_example
    from homebrewnlp_tpu.data.video import VideoDataset
    import cv2
    params = _video_params()
    rng = np.random.default_rng(0)
    path = str(tmp_path / "vid_0_100.tfrecord")
    with RecordWriter(path) as w:
        for i in range(12):
            img = rng.integers(0, 255, (8, 8, 3)).astype(np.uint8)
            ok, enc = cv2.imencode(".png", img)
            w.write(encode_example({
                "frame": enc.tobytes(), "concat": [0], "skip_frame": [0],
                "tokens": list(rng.integers(0, 32, 4)), "mask": [3]}))
    params.dataset_configs = [{"path": path, "type": "video", "weight": 1}]
    ds = VideoDataset(params, sub_batch_size=2, repeat=True)
    batch = next(iter(ds))
    p = params
    expect = (2, p.time_patch_size + 1, p.frame_height_patch,
              p.frame_width_patch, p.channel_color_size) if p.three_axes else \
        (2, p.time_patch_size + 1, p.frame_height_patch * p.frame_width_patch,
         p.channel_color_size)
    assert batch["frame"].shape == expect
    assert batch["token_x"].shape == (2, p.time_patch_size,
                                      p.language_token_patch, p.token_patch_size)
    assert batch["vid_msk_src"].dtype == bool


def mixed_dataset_test(tmp_path):
    from homebrewnlp_tpu.data.tfrecord import RecordWriter, encode_example
    from homebrewnlp_tpu.data.video import mixed_dataset
    import cv2
    params = _video_params()
    rng = np.random.default_rng(0)
    vpath = str(tmp_path / "vid_0_100.tfrecord")
    with RecordWriter(vpath) as w:
        for i in range(12):
            img = rng.integers(0, 255, (8, 8, 3)).astype(np.uint8)
            ok, enc = cv2.imencode(".png", img)
            w.write(encode_example({"frame": enc.tobytes(), "concat": [0],
                                    "skip_frame": [0],
                                    "tokens": list(rng.integers(0, 32, 4)),
                                    "mask": [3]}))
    tpath = str(tmp_path / "txt_0_600.tfrecord")
    with RecordWriter(tpath) as w:
        w.write(encode_example({"text": bytes(rng.integers(0, 32, 600).astype(np.uint8).tolist())}))
    params.dataset_configs = [{"path": vpath, "type": "video", "weight": 1},
                              {"path": tpath, "type": "text", "weight": 1}]
    it = mixed_dataset(params, sub_batch_size=2)
    keys = {"frame", "token_x", "token_y", "txt_msk", "vid_msk_src",
            "vid_msk_tgt", "cat_mask_x", "cat_mask_y"}
    for _ in range(4):
        batch = next(it)
        assert keys <= set(batch.keys())
        assert batch["frame"].dtype == np.int32
