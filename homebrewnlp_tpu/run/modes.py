"""Run-mode registry: train / sample / query / web_api / debug.

Reference: RUN_MODE_FNS in /root/reference/src/main.py:36-41.
"""
from __future__ import annotations

import typing

import jax
import numpy as np

from ..config import ModelParameter
from ..core import sharding as shardlib
from ..infer.interface import InterfaceWrapper, Tokenizer, debug_similarity, query_repl
from ..model import Model
from ..train import checkpoint as ckpt
from .train_loop import MEMBERSHIP_EXIT_CODE, PREEMPTED_EXIT_CODE
from .train_loop import train as train_loop


def _dummy_batch(params: ModelParameter, batch_size: int = 1,
                 rng: typing.Optional[np.random.Generator] = None):
    """Zero/random batch with the mode's input structure (text or video)."""
    p = params
    if rng is None:
        rng = np.random.default_rng(0)
    if not p.use_video:
        seq = p.sequence_length // p.token_patch_size
        zeros = np.zeros((batch_size, seq, p.token_patch_size), np.int32)
        return {"token_x": zeros, "token_y": zeros.copy()}
    fshape = ((batch_size, p.time_patch_size + 1, p.frame_height_patch,
               p.frame_width_patch, p.channel_color_size) if p.three_axes else
              (batch_size, p.time_patch_size + 1,
               p.frame_height_patch * p.frame_width_patch,
               p.channel_color_size))
    batch = {"frame": np.asarray(rng.integers(0, 255, fshape), np.int32)}
    ones_t = np.ones((batch_size, p.time_patch_size), np.float32)
    batch.update(vid_msk_src=ones_t, vid_msk_tgt=ones_t.copy(),
                 cat_mask_x=ones_t.copy(), cat_mask_y=ones_t.copy())
    if p.use_language:
        tshape = (batch_size, p.time_patch_size, p.language_token_patch,
                  p.token_patch_size)
        toks = rng.integers(0, p.vocab_size, tshape).astype(np.int32)
        batch.update(token_x=toks, token_y=toks.copy(),
                     txt_msk=np.ones(tshape, np.float32))
    return batch


def _load_model(params: ModelParameter, batch_size: int = 1):
    """Restore the model for a serving mode, placed on the serving mesh.

    With more than one device the restored variables are laid out over the
    config-derived ``inference_mesh`` (tensor parallelism over 'model',
    batch over 'data'; 'pipe'/'sequence' folded into 'data' — decode has no
    pipeline/ring schedule) so sample/query/web_api/debug run through the
    same device topology as training, like the reference's non-train modes
    through the SimdMeshImpl (/root/reference/src/run/run.py:200-308).
    Returns (params, model, variables, mesh); mesh is None single-device."""
    params = ModelParameter(params, train=False, train_batch_size=batch_size)
    model = Model(params)
    batch = _dummy_batch(params, batch_size=batch_size)
    variables = model.init(batch)
    # corruption fallback: serve the newest COMPLETE checkpoint instead of
    # crashing on a torn latest one (train_loop resumes the same way);
    # strict = an all-corrupt model_path refuses to serve random init
    restored = ckpt.restore_latest_valid(params.model_path, strict=True)
    if restored:
        loaded, _, step, _ = restored
        variables = {k: np.asarray(loaded[k]).astype(variables[k].dtype)
                     if k in loaded else v for k, v in variables.items()}
        print(f"loaded checkpoint at step {step}")
    else:
        print("no checkpoint found — sampling from random init")
    from ..utils.flops import describe_devices
    print(describe_devices(), flush=True)
    mesh = None
    if len(jax.devices()) > 1:
        mesh = shardlib.inference_mesh(params)
        variables = shardlib.shard_params(params, variables,
                                          model.param_dims, mesh)
    else:
        variables = {k: jax.numpy.asarray(v) for k, v in variables.items()}
    print(shardlib.placement_report(variables, mesh), flush=True)
    return params, model, variables, mesh


def train_mode(params: ModelParameter, args):
    result = train_loop(params)
    print(result)
    if result.get("membership_change"):
        # pod membership changed (a peer's lease lapsed): no emergency
        # checkpoint was possible — the elastic controller re-forms the
        # fleet at the surviving world size from the freshest complete one
        return MEMBERSHIP_EXIT_CODE
    if result.get("preempted"):
        # distinct exit code: the emergency checkpoint is written and the
        # run is resumable — scripts/run_manager.py relaunches on this code
        # instead of declaring the run finished
        return PREEMPTED_EXIT_CODE
    return 0


def sample_mode(params: ModelParameter, args):
    params, model, variables, mesh = _load_model(params)
    if params.use_video:
        _sample_video_mode(params, model, variables)
        return
    interface = InterfaceWrapper(params, model, variables, mesh=mesh)
    tok = Tokenizer(params)
    rng = np.random.default_rng(0)
    for i in range(params.num_of_sample):
        prompt = rng.integers(0, params.vocab_size, 8).astype(np.int32)
        out = interface.complete_tokens(prompt,
                                        temperature=params.sampling_temperature,
                                        seed=i)
        print(f"--- sample {i} ---")
        print(tok.decode(out))


def _sample_video_mode(params: ModelParameter, model, variables):
    """Video (jannet) sampling: autoregressive frame continuation rendered
    to .avi (reference interface.py:13-58 / inference.py:25-73)."""
    import os
    from ..infer.interface import render_video
    from ..infer.sampler import sample_video
    rng = np.random.default_rng(0)
    tok = Tokenizer(params)
    for i in range(params.num_of_sample):
        batch = _dummy_batch(params, rng=rng)
        frames01, tokens = sample_video(model, variables, batch)
        texts = None
        if tokens is not None:
            texts = [tok.decode(tokens[0, t].reshape(-1))
                     for t in range(tokens.shape[1])]
        path = render_video(frames01[0], texts, params,
                            os.path.join(params.model_path, f"sample_{i}"))
        print(f"--- sample {i}: {path} ---")


def query_mode(params: ModelParameter, args):
    params, model, variables, mesh = _load_model(params)
    query_repl(InterfaceWrapper(params, model, variables, mesh=mesh))


def web_api_mode(params: ModelParameter, args):
    replicas = int(getattr(params, "serve_replicas", 0) or 0)
    if replicas < 2 and getattr(params, "serve_replica_classes", ""):
        # a class topology (docs/SERVING.md 'Disaggregated tier') implies
        # the replica count; serve_replicated re-derives the same list
        from ..infer.router import parse_replica_classes
        replicas = len(parse_replica_classes(params.serve_replica_classes))
    if replicas >= 2:
        # multi-replica tier (docs/SERVING.md): the parent stays
        # DEVICE-FREE — each replica subprocess loads the model itself —
        # and runs the router + fleet supervisor instead of a device loop
        return _serve_replicated_mode(params)
    params, model, variables, mesh = _load_model(params)
    interface = InterfaceWrapper(params, model, variables, mesh=mesh)
    from ..infer.rest_api import serve
    # preemption-safe serving shutdown, mirroring the train loop's handlers:
    # SIGTERM/SIGINT set a stop event the device loop notices within its 1s
    # poll, so the HTTP subprocess and the IPC Manager are torn down cleanly
    # (in-flight responses are answered; no EOFError traceback at teardown)
    import signal
    import threading
    from .train_loop import _ShutdownFlag
    stop = threading.Event()
    # the train loop's handler object: one shared implementation of the
    # reentrancy-safe message write and the repeated-signal force-exit
    # (needed when the device loop is wedged inside a decode and never
    # reaches its stop-event poll)
    handler = _ShutdownFlag(
        message="draining the serve loop (repeat to force-exit)",
        on_signal=stop.set)
    previous = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            previous[sig] = signal.signal(sig, handler)
        except ValueError:  # not the main thread (embedded use) — skip
            pass
    try:
        # reference: web_workers uvicorn processes (src/rest_api.py:84-87);
        # main.py has already folded CLI --workers into params.web_workers
        serve(params, interface, workers=params.web_workers, stop=stop)
    finally:
        for sig, prev in previous.items():
            if prev is not None:  # None = installed by non-Python code;
                signal.signal(sig, prev)  # signal() rejects it


def _serve_replicated_mode(params: ModelParameter):
    """web_api with ``serve_replicas`` >= 2: router + replica fleet, with
    the same preemption-safe SIGTERM/SIGINT drain as single-replica
    serving (the fleet is terminated cleanly, not orphaned)."""
    import signal
    import threading
    from ..infer.router import serve_replicated
    from .train_loop import _ShutdownFlag
    stop = threading.Event()
    handler = _ShutdownFlag(
        message="draining the replica tier (repeat to force-exit)",
        on_signal=stop.set)
    previous = {}
    for sig in (signal.SIGTERM, signal.SIGINT):
        try:
            previous[sig] = signal.signal(sig, handler)
        except ValueError:
            pass
    try:
        serve_replicated(params, workers=params.web_workers, stop=stop)
    finally:
        for sig, prev in previous.items():
            if prev is not None:
                signal.signal(sig, prev)


def debug_mode(params: ModelParameter, args):
    params, model, variables, mesh = _load_model(params)
    interface = InterfaceWrapper(params, model, variables, mesh=mesh)
    debug_similarity(interface)
    from ..infer.interface import debug_sample_check
    debug_sample_check(interface)


def analyze_mode(params: ModelParameter, args):
    """Standalone model analysis: build (meshless, no device compute beyond
    init) and print the parameter-count report without training — the
    reference only ran analyze_model as a train-startup side effect
    (src/run/utils_run.py:65-113); this exposes it as its own mode so a
    config can be inspected before committing any compute to it."""
    from .analysis import analyze_model
    model = Model(params)
    variables = model.init(_dummy_batch(params,
                                        batch_size=params.train_batch_size))
    # chief-only model_size.info write, like the train loop's call site
    # (one shared model_path on multi-host pods)
    analyze_model(params, variables, model.param_dims,
                  dump=jax.process_index() == 0)


RUN_MODE_FNS: typing.Dict[str, typing.Callable] = {
    "train": train_mode,
    "sample": sample_mode,
    "debug_old": sample_mode,  # reference alias (src/main.py:36)
    "query": query_mode,
    "web_api": web_api_mode,
    "debug": debug_mode,
    "analyze": analyze_mode,   # new: config inspection without training
}
