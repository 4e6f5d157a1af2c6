"""Inference interface: tokenisation, CLI query REPL, debug similarity mode.

Reference: /root/reference/src/interface.py
 — byte-level or GPT2-BPE detokenisation (:61-88), interactive query REPL
(:177-220), and the `debug` run mode that scores output similarity across
parallel identical queries (:283-302), which doubles as an SPMD-divergence
check.
"""
from __future__ import annotations

import typing

import numpy as np

from ..config import ModelParameter
from ..model import Model
from .sampler import sample_text


class Tokenizer:
    """Byte-level for vocab<=256; GPT2-BPE via transformers otherwise
    (matching the reference's convention)."""

    def __init__(self, params: ModelParameter):
        self.params = params
        self._bpe = None
        if params.vocab_size > 256:
            try:
                from transformers import GPT2TokenizerFast
                self._bpe = GPT2TokenizerFast.from_pretrained("gpt2")
            except Exception:
                self._bpe = None

    def encode(self, text: str) -> np.ndarray:
        if self._bpe is not None:
            return np.asarray(self._bpe.encode(text), np.int32)
        return np.frombuffer(text.encode("utf-8", "replace"), np.uint8
                             ).astype(np.int32) % self.params.vocab_size

    def decode(self, tokens: typing.Sequence[int]) -> str:
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        if self._bpe is not None:
            return self._bpe.decode(toks)
        return bytes(t % 256 for t in toks).decode("utf-8", "replace")


def model_width_view(params: ModelParameter, model: Model, width: int):
    """A batch-``width`` ``(params, Model)`` view over the SAME variables.

    The block plan and parameter dims are batch-size independent
    (``BlockSpec = (depth, cfg, names)``), so the view shares them instead
    of re-running init — which would materialise, and discard, a full
    host-numpy copy of every parameter per width.  One definition serves
    the serving interface's width cache AND the speculative draft's width
    view (infer/spec.py), so batch-independent model attributes cannot
    silently diverge between the two."""
    p = ModelParameter(params, train_batch_size=width)
    p.train = False
    m = Model(p)
    m.plan = model.plan
    m.param_dims = dict(model.param_dims)
    m.param_fan_in = dict(getattr(model, "param_fan_in", {}))
    m.quant_scales = getattr(model, "quant_scales", None)
    return p, m


class InterfaceWrapper:
    """complete(prompt, temperature, response_len) over a loaded model.

    ``mesh``: optional serving mesh (core/sharding.py ``inference_mesh``) —
    completions then run tensor/data-parallel over it, with the variables
    expected to already carry their NamedShardings (run/modes.py
    ``_load_model``)."""

    def __init__(self, params: ModelParameter, model: Model, variables,
                 mesh=None):
        self.params = params
        self.model = model
        self.variables = variables
        self.mesh = mesh
        if getattr(params, "serve_quantized_weights", False):
            # weight-only int8 for the decode matvecs (core/quant.py):
            # batch-1 decode is weight-read bound, int8 halves the bytes
            from ..core.quant import quantize_variables
            self.variables, scales = quantize_variables(
                variables, model.param_dims, model.param_fan_in)
            model.quant_scales = scales
        self.tokenizer = Tokenizer(params)
        # decode-call counter: the REST batching test pins that N concurrent
        # completions share device calls instead of running N serial decodes
        self.decode_calls = 0
        # batch-width -> (params, Model) views over the SAME variables: the
        # batch dim is static in the named-dim substrate, so each distinct
        # serving batch width needs its own abstract plan (eval_shape only —
        # no device memory); widths are powers of two, so the cache is tiny
        self._width_models: typing.Dict[int, tuple] = {
            params.train_batch_size: (params, model)}

    def _model_for_width(self, width: int):
        if width not in self._width_models:
            self._width_models[width] = model_width_view(self.params,
                                                         self.model, width)
        return self._width_models[width]

    def decode_path(self, width: typing.Optional[int] = None) -> dict:
        """Which decode loop serves ``width``-wide batches and why — ops
        surface for the REST ``/health`` endpoint.  The stepped loop's
        in-place cache carry is what makes big-context serving viable
        (docs/PERFORMANCE.md 'Big-cache decode'), so whether a deployment
        actually routes through it should be observable, not inferred."""
        from .sampler import _use_stepped_loop, decode_cache_bytes
        p = self.params
        # default to the deployment's MAX batched-serving width (the device
        # loop drains up to serve_batch_size requests into one decode):
        # cache bytes scale with width, so reporting the training batch
        # width would misstate which loop real traffic decodes through
        serve_max = max(1, int(getattr(p, "serve_batch_size", 1) or 1))
        width = int(width or serve_max)
        # clamp to widths the serving path can actually run, then round up
        # to its power-of-two padding — /health is client-reachable, so an
        # arbitrary width must not grow the per-width model cache unbounded
        # (each distinct width builds and caches a plan view) or stall the
        # device loop behind a giant eval_shape trace
        width = min(max(width, 1), max(serve_max, p.train_batch_size))
        pow2 = 1
        while pow2 < width:
            pow2 *= 2
        width = pow2
        _, model_w = self._model_for_width(width)
        seq = p.sequence_length // p.token_patch_size
        token_shape = np.zeros((width, seq, p.token_patch_size), np.int32)
        try:
            cache_bytes = decode_cache_bytes(model_w, self.variables,
                                             token_shape)
            stepped = _use_stepped_loop(model_w, self.variables, token_shape)
        except NotImplementedError:
            # a layer without a streaming form serves via the full-forward
            # fallback; there is no cache to report
            return {"loop": "full_forward_fallback", "batch_width": width}
        return {"loop": "stepped" if stepped else "fused",
                "configured": p.decode_loop,
                "batch_width": width,
                "cache_gb": round(cache_bytes / 1024 ** 3, 3),
                "chunk_tokens": int(p.decode_chunk_tokens),
                "cache_dtype": str(p.decode_cache_dtype or
                                   p.calculation_dtype)}

    @property
    def prompt_capacity(self) -> int:
        """Longest prompt (in tokens) a completion can consume: one token
        position must remain for generation, so ``complete_tokens`` CLIPS
        prompts to ``seq - 1``.  The REST layer reads this to surface
        ``"truncated": true`` instead of letting a clipped prompt look like
        a short answer (rest_api._handlers / _complete_batch)."""
        return self.params.sequence_length // self.params.token_patch_size - 1

    def complete_tokens(self, tokens: np.ndarray, temperature: float = 0.0,
                        response_len: typing.Optional[int] = None,
                        seed: int = 0, top_k: int = None,
                        top_p: float = None,
                        repetition_penalty: float = None) -> np.ndarray:
        seq = self.params.sequence_length // self.params.token_patch_size
        prompt_len = min(len(tokens), seq - 1)
        end = seq if response_len is None else min(seq, prompt_len + response_len)
        self.decode_calls += 1
        out = sample_text(self.model, self.variables, tokens[None, :prompt_len],
                          initial_pos=prompt_len, temperature=temperature,
                          end_iterations=end, seed=seed,
                          pad_random=True,  # reference interface.py:263
                          mesh=self.mesh, top_k=top_k, top_p=top_p,
                          repetition_penalty=repetition_penalty)
        return out[0, :end, 0] if out.ndim == 3 else out[0, :end]

    def complete_tokens_batch(self, token_lists, temperatures=None,
                              response_lens=None, seed: int = 0,
                              top_ks=None, top_ps=None, rep_penalties=None
                              ) -> typing.List[np.ndarray]:
        """N prompts -> one decode call (decode is cache-read-bandwidth
        bound: batch 8 is ~4x the aggregate throughput of batch 1,
        BASELINE.md 'Decoding').  Per-row prompt lengths and temperatures
        ride the samplers' batched ``initial_pos``/``temperature``; the
        batch pads to the next power of two (bounded compile count) with
        inert rows (initial_pos = seq - 1)."""
        n = len(token_lists)
        if n == 0:
            return []
        p = self.params
        seq = p.sequence_length // p.token_patch_size
        tps = p.token_patch_size
        if temperatures is None:
            temperatures = [0.0] * n
        if response_lens is None:
            response_lens = [None] * n
        width = 1
        while width < n:
            width *= 2
        rng = np.random.default_rng(seed)
        token_x = rng.integers(0, p.vocab_size, (width, seq, tps)
                               ).astype(np.int32)  # pad_random, ref :263
        ip = np.full(width, seq - 1, np.int32)
        temps = np.zeros(width, np.float32)
        # per-row logits filters; rows without an explicit request value
        # fall back to the config serving defaults (sampling_top_k/top_p),
        # matching the single-request path's fallback in sample_text.
        # Pad rows keep the defaults too — they are inert (initial_pos =
        # seq - 1) and produce no output
        tks = np.full(width, p.sampling_top_k, np.int32)
        tps_arr = np.full(width, p.sampling_top_p, np.float32)
        reps = np.full(width, p.sampling_repetition_penalty, np.float32)
        ends = []
        for i, toks in enumerate(token_lists):
            toks = np.asarray(toks).reshape(-1)[:seq - 1]
            # broadcast across ALL patch lanes, matching the serial path
            # (sampler.py prompt[:, :, None] -> token_x[:, :n]); lane-0-only
            # writes would leave random pad in the upper lanes at tps > 1
            token_x[i, :len(toks), :] = toks[:, None]
            ip[i] = len(toks)
            temps[i] = float(temperatures[i])
            if top_ks is not None and top_ks[i] is not None:
                tks[i] = int(top_ks[i])
            if top_ps is not None and top_ps[i] is not None:
                tps_arr[i] = float(top_ps[i])
            if rep_penalties is not None and rep_penalties[i] is not None:
                reps[i] = float(rep_penalties[i])
            rl = response_lens[i]
            ends.append(seq if rl is None else min(seq, len(toks) + int(rl)))
        self.decode_calls += 1
        _, model_w = self._model_for_width(width)
        out = sample_text(model_w, self.variables, token_x,
                          initial_pos=ip, temperature=temps,
                          end_iterations=max(ends), seed=seed,
                          mesh=self.mesh, top_k=tks, top_p=tps_arr,
                          repetition_penalty=reps)
        if out.ndim == 3:
            out = out[:, :, 0]
        return [out[i, :ends[i]] for i in range(n)]

    def complete(self, query: str, temperature: float = 0.0,
                 response_len: typing.Optional[int] = None, seed: int = 0,
                 top_k: int = None, top_p: float = None,
                 repetition_penalty: float = None) -> str:
        tokens = self.tokenizer.encode(query)
        out = self.complete_tokens(tokens, temperature, response_len, seed,
                                   top_k=top_k, top_p=top_p,
                                   repetition_penalty=repetition_penalty)
        return self.tokenizer.decode(out[len(tokens):])


def query_repl(interface: InterfaceWrapper):
    """Interactive REPL (reference interface.py:177-220)."""
    print("query mode — empty line to exit")
    while True:
        try:
            prompt = input("prompt> ")
        except EOFError:
            return
        if not prompt:
            return
        try:
            temp = float(input("temperature (default "
                               f"{interface.params.sampling_temperature})> ") or
                         interface.params.sampling_temperature)
        except ValueError:
            temp = interface.params.sampling_temperature
        print(interface.complete(prompt, temperature=temp))


def debug_sample_check(interface: InterfaceWrapper, seed: int = 0) -> float:
    """Teacher-forced vs autoregressive agreement (reference
    interface.py:146-151 / the ``debug_sample`` flag): run one greedy
    autoregressive completion, then teacher-force the produced sequence and
    check each step's argmax reproduces the sampled token."""
    import jax
    import jax.numpy as jnp
    params = interface.params
    rng = np.random.default_rng(seed)
    prompt = rng.integers(0, params.vocab_size, 8).astype(np.int32)
    out = interface.complete_tokens(prompt, temperature=0.0, seed=seed)
    seq = params.sequence_length // params.token_patch_size
    token_x = np.zeros((1, seq, params.token_patch_size), np.int32)
    token_x[0, :len(out), 0] = out[:seq]
    info = interface.model.apply(interface.variables,
                                 {"token_x": jnp.asarray(token_x),
                                  "token_y": jnp.asarray(token_x)},
                                 mesh=interface.mesh)
    logits = np.asarray(info.token_out.data, np.float32)[0, :, 0]
    preds = logits.argmax(-1)
    start = min(len(prompt), seq - 1)
    # prediction at p-1 generates the token at p
    agree = np.mean(preds[start - 1:seq - 1] == out[start:seq])
    print(f"debug_sample teacher-forcing agreement: {agree:.3f}")
    return float(agree)


def debug_similarity(interface: InterfaceWrapper, n: typing.Optional[int] = None
                     ) -> float:
    """Spawn identical queries and score token agreement
    (reference interface.py:283-302); with temperature 0 the outputs must be
    identical — a runtime determinism / SPMD-divergence check."""
    params = interface.params
    n = n or params.equal_debugging_items_per_check
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, params.vocab_size, 8).astype(np.int32)
    outs = [interface.complete_tokens(prompt, temperature=0.0, seed=0)
            for _ in range(n)]
    matches = sum(np.array_equal(outs[0], o) for o in outs[1:])
    score = matches / max(1, len(outs) - 1)
    print(f"debug similarity: {score:.3f} ({matches}/{len(outs) - 1} identical)")
    return score


def unpatchify(frames, params):
    """Invert the input pipeline's patchify transpose (data/video.py:60:
    memory order [ps, ps, hp, wp, c] regardless of the three_axes view):
    [seq, ...] -> [seq, frame_height, frame_width, c]."""
    import numpy as np
    frames = np.asarray(frames)
    seq = frames.shape[0]
    hp, wp, ps = (params.frame_height_patch, params.frame_width_patch,
                  params.patch_size)
    c = params.color_channels
    return (frames.reshape(seq, ps, ps, hp, wp, c)
            .transpose(0, 3, 1, 4, 2, 5)
            .reshape(seq, params.frame_height, params.frame_width, c))


def render_video(frames01, texts, params, path: str, upscale: int = 4,
                 fps: int = 1, line_split: int = 2):
    """Write sampled frames to an MJPG .avi with token-text overlay
    (reference interface.py:13-58 semantics, numpy nearest-neighbour
    upscaling instead of scipy).  ``frames01``: float [seq, ...] in the
    input pipeline's patchified layout (data/video.py:60: memory order
    [ps, ps, hp, wp, c]), values in [0, 1]; ``texts``: per-frame strings or
    None.  Falls back to an .npz dump without cv2 / for bit-folded frames."""
    import numpy as np
    import os
    frames01 = np.asarray(frames01)
    h, w = params.frame_height, params.frame_width
    c = params.color_channels
    seq = frames01.shape[0]
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)

    def _dump():
        np.savez(path + ".npz", frames=frames01,
                 texts=np.asarray(texts if texts is not None else []))
        return path + ".npz"

    if params.use_bit_fold_input_pipeline or c != 3:
        return _dump()  # packed ints / non-BGR channel counts
    try:
        frames = unpatchify(frames01, params)
    except ValueError:
        return _dump()
    try:
        import cv2
    except ImportError:
        return _dump()
    out_path = path if path.endswith(".avi") else path + ".avi"
    writer = cv2.VideoWriter(out_path, cv2.VideoWriter_fourcc(*"MJPG"), fps,
                             (w * upscale, h * upscale))
    if not writer.isOpened():
        return _dump()
    for idx in range(seq):
        img = np.uint8(np.clip(frames[idx], 0, 1) * 255)
        img = img.repeat(upscale, axis=0).repeat(upscale, axis=1)
        img = cv2.cvtColor(img, cv2.COLOR_RGB2BGR)
        if texts is not None and idx < len(texts) and texts[idx]:
            text = texts[idx]
            step = max(1, len(text) // line_split)
            for i in range(0, len(text), step):
                cv2.putText(img, text[i:i + step],
                            (10, 20 + 24 * (i // step)),
                            cv2.FONT_HERSHEY_SIMPLEX, 0.5, (255, 0, 255), 1)
        if params.use_autoregressive_sampling:
            label = ("prompt" if idx < params.initial_autoregressive_position
                     else "sample")
            cv2.putText(img, label, (10, h * upscale - 10),
                        cv2.FONT_HERSHEY_SIMPLEX, 0.5, (0, 128, 255), 1)
        writer.write(img)
    writer.release()
    return out_path
