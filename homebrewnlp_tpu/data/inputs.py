"""Input pipeline: sharded TFRecord text datasets with deterministic resume.

Reference: /root/reference/src/inputs.py.  Same structure, no tf.data:

- ``split_files``: deterministic filename shard per dataset-holding host
  (inputs.py:15-30) with resume skips from the run log.
- ``simulate_data_pipeline``: replays the run log to compute exact per-file
  element skips so restarts resume exactly where they left off even across
  batch/ctx changes (inputs.py:33-128).  Requires the reference's filename
  convention ``..._<tokencount>.tfrecord``.
- windowed token stream per record: window size ctx+patch, shift ctx
  (inputs.py:247-249); byte records vs int64 records chosen by the
  ``'int64' in filename`` convention (inputs.py:350,553).
- static-group round-robin interleave over ``interleaved_datasets`` files
  (the same model the resume simulator replays, making resume bit-exact —
  see ``simulate_data_pipeline``), weighted mixing across dataset configs,
  background prefetch (the reference serialized infeed after compute,
  run.py:251-256 — prefetch here overlaps host decode with device steps).
"""
from __future__ import annotations

import json
import os
import queue
import random
import threading
import typing

import numpy as np

from ..config import ModelParameter
from ..telemetry import span
from . import native_recordio
from .tfrecord import decode_example, read_records


def split_files(filenames: typing.List[str], slice_index: int, slice_count: int,
                seed: int, runs_log=None, interleave: int = None):
    """Deterministic per-slice file shard with resume state.

    Returns ``(files, token_skips, phase, repeat_files)`` for this slice.
    ``phase`` is the round-robin position inside the first interleave group
    at which the resumed stream must continue; it is non-zero only when
    ``runs_log`` is given, the log's last run used the same
    ``(slice_count, interleave)``, and that run was cut mid-group.
    ``repeat_files`` is the slice's FULL file list: repeat passes (epoch 2+)
    of the stream iterate it — resuming must not drop already-consumed files
    from later epochs.  Pass all of it to ``_InterleavedStream``.
    """
    if not filenames:
        raise ValueError("no input files")
    files = sorted(filenames)
    if seed != 0:
        rng = random.Random(seed)
        rng.shuffle(files)
    all_slice = files[slice_index::slice_count]

    element_skip = [0] * len(files)
    phase = 0
    if runs_log:
        file_list_skip, element_skip, resume = simulate_data_pipeline(runs_log, files)
        files = [files[i] for i, s in enumerate(file_list_skip) if not s]
        element_skip = [element_skip[i] for i, s in enumerate(file_list_skip) if not s]
        if (resume["slice_count"] == slice_count
                and (interleave is None or resume["interleave"] == interleave)):
            phase = resume["phases"][slice_index]
    return (files[slice_index::slice_count],
            element_skip[slice_index::slice_count], phase, all_slice)


def _tokens_in_name(path: str) -> int:
    return int(str(path).split('_')[-1].replace('.tfrecord', ''))


def _usable_tokens(count: int, ctx: int, tps: int) -> int:
    """Tokens of ``count`` that produce windows: ``windows * ctx`` where
    windows = number of (ctx+tps)-sized, ctx-shifted windows in ``count``."""
    return max(count - ((count - tps) % ctx) - tps, 0)


def simulate_data_pipeline(runs_log, file_list):
    """Replay the run log -> exact resume state for the interleaved stream.

    Returns ``(file_list_skip, element_skip, resume)``:

    * ``file_list_skip[i]`` — drop file ``i`` entirely (it belongs to a fully
      consumed interleave group).  Fully consumed files inside a PARTIALLY
      consumed group are kept (with a full-token skip) so that group
      membership — and therefore the round-robin order — is identical on
      resume.
    * ``element_skip[i]`` — tokens already consumed from the start of file
      ``i``; ``_file_windows`` skips them before windowing.
    * ``resume`` — ``{"phases": [per-slice next-draw index within the first
      surviving group], "slice_count": ..., "interleave": ...}`` describing
      the state after the log's LAST run (only valid for a new run with the
      same slice/interleave geometry; ``split_files`` checks).

    Invariants (tested in tests/data_test.py::resume_continuation_*):

    * For ``slice_count == 1`` the resumed stream continues BIT-EXACTLY with
      the windows an uninterrupted stream would yield next, for ANY cut
      point — including mid-interleave-group cuts and cuts after the stream
      wrapped past the end of the dataset (``repeat=True``).
    * For ``slice_count > 1`` the same holds per slice as long as group
      consumption is symmetric across slices (equal file sizes); otherwise
      re-slicing after dropped groups can reassign files between slices and
      only the global no-window-lost/no-window-duplicated multiset property
      holds (same as the reference, /root/reference/src/inputs.py:33-128).
    * With multiple weighted datasets, per-dataset consumption is estimated
      as if all windows came from that dataset (reference behaviour);
      resume is exact only for single-text-dataset configs.

    The executed pipeline (``_InterleavedStream``) uses STATIC interleave
    groups — round-robin within a group of ``interleave_size`` files, moving
    to the next group only when the current one is exhausted — precisely the
    model replayed here, so the arithmetic is exact for unequal file sizes
    too (tf.data's dynamic slot-replacement interleave, which the reference
    used, diverges from the reference's own replay arithmetic in that case).
    """
    counts = [_tokens_in_name(f) for f in file_list]
    n = len(counts)
    file_list_skip = [False] * n
    element_skip = [0] * n
    phases: typing.List[int] = [0]
    prev_key = None
    slice_count = interleave_size = 1

    for run in runs_log:
        slice_count = run['slice_count']
        ctx = run['ctx']
        interleave_size = run['interleave_size']
        tps = run['token_patch_size']
        stop0 = run['steps'] * run['grad_accumulation'] * (run['batch_size'] // slice_count)

        live = [i for i in range(n) if not file_list_skip[i]]
        key = (slice_count, interleave_size)
        carry = phases if prev_key == key and len(phases) == slice_count \
            else [0] * slice_count
        phases = []
        final_lists = []
        for s in range(slice_count):
            phase, final_idx = _replay_slice(
                live[s::slice_count], list(range(s, n, slice_count)), counts,
                element_skip, file_list_skip, ctx, tps, interleave_size,
                stop0, carry[s])
            phases.append(phase)
            final_lists.append(final_idx)
        prev_key = key

        # Keep fully-consumed files inside partially-consumed groups so that
        # group membership is preserved on resume; drop whole groups only.
        # The groups of the run's FINAL pass (the live list for pass 1, the
        # full slice list after a wrap) define membership.
        for idx in final_lists:
            for gs in range(0, len(idx), interleave_size):
                grp = idx[gs:gs + interleave_size]
                full = all(file_list_skip[i] for i in grp)
                for i in grp:
                    file_list_skip[i] = full

    return file_list_skip, element_skip, {
        "phases": phases, "slice_count": slice_count,
        "interleave": interleave_size}


def _replay_slice(live_idx, all_idx, counts, element_skip, file_list_skip,
                  ctx, tps, interleave, stop, phase):
    """Replay one slice's stream for one run, mutating ``element_skip`` /
    ``file_list_skip``.  Pass 1 runs over ``live_idx`` (the resumed view);
    repeat passes reopen the slice's FULL list ``all_idx`` with no skips —
    already-consumed files come back in later epochs.  Returns ``(phase,
    final_idx)``: the round-robin position inside the group the run was cut
    in (0 on a group boundary) and the file list whose groups formed the
    final pass."""
    first_pass = True
    while True:
        idx = live_idx if first_pass else all_idx
        rem = [_usable_tokens(counts[i] - element_skip[i], ctx, tps) if first_pass
               else _usable_tokens(counts[i], ctx, tps) for i in idx]
        if not first_pass:
            # Wrapped past the end: the stream reopens the full slice list
            # with no skips.  Clear the slice's consumption and fast-forward
            # whole passes.
            total = sum(rem) // ctx
            if total == 0:
                return 0, idx
            for i in idx:
                element_skip[i] = 0
                file_list_skip[i] = False
            stop %= total
        for gs in range(0, len(idx), interleave):
            grp = list(range(gs, min(gs + interleave, len(idx))))
            total = sum(rem[g] for g in grp) // ctx
            start = phase if first_pass and gs == 0 else 0
            phase = 0
            if stop >= total:
                stop -= total
                for g in grp:
                    element_skip[idx[g]] += rem[g]
                    file_list_skip[idx[g]] = True
                if stop == 0:
                    return 0, idx
            else:
                i = min(start, len(grp) - 1)
                while stop > 0:
                    while rem[grp[i]] <= 0:
                        i = (i + 1) % len(grp)
                    rem[grp[i]] -= ctx
                    element_skip[idx[grp[i]]] += ctx
                    stop -= 1
                    i = (i + 1) % len(grp)
                for g in grp:
                    if rem[g] <= 0:
                        file_list_skip[idx[g]] = True
                return i, idx
        if stop <= 0:
            return 0, idx
        first_pass = False


# ---- token extraction ----------------------------------------------------

def _record_tokens(payload: bytes, int_tokens: bool) -> np.ndarray:
    fast = native_recordio.feature_tokens(payload, "text")
    if fast is not None:
        return fast.astype(np.int32)
    ex = decode_example(payload)
    value = ex.get("text", b"")
    if isinstance(value, (bytes, bytearray)):
        return np.frombuffer(bytes(value), dtype=np.uint8).astype(np.int32)
    return np.asarray(value, dtype=np.int32)


def _file_windows(path: str, ctx: int, patch: int, skip_tokens: int,
                  int_tokens: bool) -> typing.Iterator[np.ndarray]:
    """Windows (size ctx+patch, shift ctx) per record; a leading token skip is
    consumed from the file's first records (deterministic-resume support)."""
    remaining_skip = skip_tokens
    for payload in read_records(path):
        tokens = _record_tokens(payload, int_tokens)
        if remaining_skip:
            if remaining_skip >= len(tokens):
                remaining_skip -= len(tokens)
                continue
            tokens = tokens[remaining_skip:]
            remaining_skip = 0
        n = len(tokens)
        window = ctx + patch
        if n < window:
            continue
        starts = range(0, n - window + 1, ctx)
        for s in starts:
            yield tokens[s:s + window]


class _InterleavedStream:
    """Round-robin over STATIC groups of ``cycle`` files: files are processed
    in consecutive groups of ``cycle``; windows are drawn round-robin within
    the group (exhausted members are dropped from the rotation) and the next
    group opens only once the current one is fully drained.

    This is exactly the model ``simulate_data_pipeline`` replays, which makes
    deterministic resume exact for any file sizes.  ``phase`` is the resume
    round-robin position inside the FIRST group (from ``split_files``);
    ``skips`` apply to the first pass only — on ``repeat`` the stream reopens
    ``repeat_files`` (the slice's full, unfiltered file list — consumed files
    dropped from the resume pass come back in later epochs) with no skips.
    """

    def __init__(self, files, skips, ctx, patch, cycle, int_tokens, repeat,
                 phase: int = 0, repeat_files=None):
        self.files = list(files)
        self.skips = list(skips) if skips else [0] * len(self.files)
        self.ctx = ctx
        self.patch = patch
        self.cycle = max(1, cycle)
        self.int_tokens = int_tokens
        self.repeat = repeat
        self.phase = phase
        self.repeat_files = list(repeat_files) if repeat_files is not None \
            else list(files)

    def __iter__(self):
        first_pass = True
        while True:
            files = self.files if first_pass else self.repeat_files
            skips = self.skips if first_pass else None
            n = len(files)
            for start in range(0, n, self.cycle):
                group = [
                    _file_windows(files[j], self.ctx, self.patch,
                                  skips[j] if skips else 0, self.int_tokens)
                    for j in range(start, min(start + self.cycle, n))]
                i = min(self.phase, len(group) - 1) if first_pass and start == 0 \
                    else 0
                while group:
                    try:
                        yield next(group[i])
                        i = (i + 1) % len(group)
                    except StopIteration:
                        del group[i]
                        if group:
                            i %= len(group)
            if not self.repeat or not self.repeat_files:
                return
            first_pass = False


def _expand_glob(path: str) -> typing.List[str]:
    from ..utils import fs
    if any(c in path for c in "*?["):
        return fs.glob(path)
    if fs.isdir(path):
        return sorted(fs.join(path, f) for f in fs.listdir(path))
    return [path]


def _shuffle_windows(it, buffer_size: int, rng):
    """tf.data-style buffered shuffle: keep ``buffer_size`` windows, yield a
    random one, refill (reference inputs.py:561-563 under
    use_random_dataloader)."""
    buf = []
    for item in it:
        buf.append(item)
        if len(buf) >= buffer_size:
            idx = int(rng.integers(len(buf)))
            buf[idx], buf[-1] = buf[-1], buf[idx]
            yield buf.pop()
    rng.shuffle(buf)
    yield from buf


class TextDataset:
    """gpt_neo_input equivalent (reference inputs.py:528-566): yields
    {'token_x', 'token_y'} int32 batches of shape [batch, seq/tps, tps].

    With ``use_random_dataloader`` the window stream is shuffled through a
    ``shuffle_buffer``-sized buffer with an UNSEEDED rng (and the caller
    skips run-log resume): the reference's randomized debug pipeline
    (inputs.py:540-563, dataloader_placement.py:121)."""

    def __init__(self, params: ModelParameter, sub_batch_size: int,
                 slice_index: int = 0, slice_count: int = 1, runs_log=None,
                 repeat: bool = True, dataset_configs=None,
                 holdout: typing.Optional[typing.Tuple[str, int]] = None):
        """``dataset_configs`` overrides ``params.dataset_configs`` (the eval
        pass feeds ``eval_dataset_configs`` through the same machinery).
        ``holdout=("train"|"eval", n)``: with no explicit eval datasets, the
        LAST n files (sorted order, deterministic) of every glob are held out
        of the training side and form the eval side (config
        ``eval_holdout_files``)."""
        self.params = params
        self.sub_batch_size = sub_batch_size
        streams = []
        weights = []
        configs = (params.dataset_configs if dataset_configs is None
                   else dataset_configs)
        for cfg in configs:
            if cfg.get('type', 'text') != 'text':
                continue
            filenames = []
            for pattern in ([cfg['path']] if isinstance(cfg['path'], str) else cfg['path']):
                filenames.extend(_expand_glob(pattern))
            if holdout is not None and holdout[1] > 0:
                side, n = holdout
                filenames = sorted(set(filenames))
                if n >= len(filenames):
                    # raise on BOTH sides: the train side has nothing left,
                    # and a standalone eval side would silently score the
                    # entire training set as "held-out"
                    raise ValueError(
                        f"eval_holdout_files={n} holds out every file of "
                        f"{cfg['path']!r} ({len(filenames)} files) — the "
                        "split would leave no training data and the eval "
                        "set would equal the full dataset")
                filenames = filenames[-n:] if side == "eval" \
                    else filenames[:-n]
            files, skips, phase, all_files = split_files(
                filenames, slice_index, slice_count,
                params.data_seed * int(params.shuffle_input_filenames), runs_log,
                interleave=params.interleaved_datasets)
            int_tokens = bool(all_files) and 'int64' in all_files[0]
            patch = params.token_patch_size * params.output_offset
            streams.append(_InterleavedStream(files, skips, params.sequence_length,
                                              patch, params.interleaved_datasets,
                                              int_tokens, repeat, phase=phase,
                                              repeat_files=all_files))
            weights.append(float(cfg.get('weight', 1)))
        if not streams:
            raise ValueError("no text dataset configs")
        self.streams = streams
        total = sum(weights)
        self.weights = [w / total for w in weights]
        self.rng = np.random.default_rng(params.data_seed)

    def __iter__(self):
        p = self.params
        its = [iter(s) for s in self.streams]
        if p.use_random_dataloader:
            # deliberately unseeded: use_random_dataloader asks for fresh
            # shuffle entropy per run  # graft-lint: allow[unseeded-rng]
            shuffle_rng = np.random.default_rng()
            its = [_shuffle_windows(it, p.shuffle_buffer, shuffle_rng)
                   for it in its]
        seq_patches = p.sequence_length // p.token_patch_size
        tps = p.token_patch_size
        off = p.output_offset
        while True:
            windows = []
            while len(windows) < self.sub_batch_size:
                idx = 0 if len(its) == 1 else \
                    int(self.rng.choice(len(its), p=self.weights))
                try:
                    windows.append(next(its[idx]))
                except StopIteration:
                    if len(its) == 1:
                        return
                    del its[idx]
                    w = self.weights[:idx] + self.weights[idx + 1:]
                    total = sum(w)
                    self.weights = [x / total for x in w]
                    if not its:
                        return
            block = np.stack(windows).astype(np.int32)
            block = block.reshape(self.sub_batch_size, seq_patches + off, tps)
            x = block[:, :seq_patches]
            y = block[:, off:seq_patches + off] if off > 0 else block[:, :seq_patches]
            yield {"token_x": x, "token_y": y}


class Prefetcher:
    """Background-thread prefetch: overlap host decode with device compute
    (the reference serialized infeed after the step, run.py:251-256).

    ``close()`` releases an abandoned prefetcher: without it the fill
    thread stays blocked on its full queue forever, pinning the source
    iterator's open file buffers.

    ``telemetry_label``: when set (the train loop passes it under
    ``telemetry_enabled``), the prefetcher records a queue-depth gauge,
    fill-stall and bounded-put retry counters, and item totals into the
    process registry under ``queue=<label>`` (docs/OBSERVABILITY.md).
    None (the default) makes zero registry calls per item.

    Spans (telemetry/spans.py): ``setup/data_first_batch`` from construction
    to the first item handed out (once: always recorded — a cold pipeline's
    first decode is set-up time) and ``data/next`` around every consumer
    wait (recorded under ``telemetry_label`` only; annotated always)."""

    def __init__(self, iterable, depth: int = 2,
                 telemetry_label: typing.Optional[str] = None):
        self.q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._done = object()
        self._stop = False
        self._error: typing.Optional[BaseException] = None
        self._tel = None
        self._first = span("setup/data_first_batch")
        self._first.__enter__()
        if telemetry_label is not None:
            from ..telemetry import registry as _reg
            r = _reg()
            lab = dict(queue=telemetry_label)
            self._tel = (
                r.gauge("hbnlp_prefetch_queue_depth",
                        "items buffered ahead of the consumer",
                        ("queue",)).labels(**lab),
                r.counter("hbnlp_prefetch_fill_stalls_total",
                          "fill-thread put timeouts on a full queue (the "
                          "device outran the loader: good) ",
                          ("queue",)).labels(**lab),
                r.counter("hbnlp_prefetch_items_total",
                          "items handed to the consumer",
                          ("queue",)).labels(**lab),
                r.counter("hbnlp_prefetch_consumer_waits_total",
                          "consumer get() calls that found the queue empty "
                          "(the loader is the bottleneck: bad)",
                          ("queue",)).labels(**lab),
            )
        self.thread = threading.Thread(target=self._fill, args=(iterable,),
                                       daemon=True,
                                       name="prefetcher-fill")
        self.thread.start()

    def _fill(self, iterable):
        tel = self._tel
        try:
            for item in iterable:
                while not self._stop:
                    try:
                        self.q.put(item, timeout=0.2)
                        if tel is not None:
                            tel[0].set(self.q.qsize())
                        break
                    except queue.Full:
                        if tel is not None:
                            tel[1].inc()
                        continue
                if self._stop:
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised in the consumer
            # capture for __next__: the done sentinel below would otherwise
            # make a decode/IO crash indistinguishable from dataset
            # exhaustion, and train() would exit cleanly at the wrong step
            self._error = e
        finally:
            # the sentinel must not be dropped on a momentarily-full queue
            # (the consumer would drain the real items then block forever);
            # same bounded-wait put as the items, abandoned only on close()
            while not self._stop:
                try:
                    self.q.put(self._done, timeout=0.2)
                    break
                except queue.Full:
                    continue

    def close(self):
        """Stop the fill thread and drop queued items; idempotent."""
        self._stop = True
        try:
            while True:
                self.q.get_nowait()
        except queue.Empty:
            pass
        self.thread.join(timeout=5)

    def __iter__(self):
        return self

    def __next__(self):
        tel = self._tel
        if tel is not None and self.q.qsize() == 0:
            tel[3].inc()
        with span("data/next", record=tel is not None):
            item = self.q.get()
        if self._first is not None:
            self._first.__exit__(None, None, None)
            self._first = None
        if item is self._done:
            if self._error is not None:
                error, self._error = self._error, None
                raise error
            raise StopIteration
        if tel is not None:
            tel[2].inc()
            tel[0].set(self.q.qsize())
        return item


# ---- run log (DataLog) ---------------------------------------------------

def runs_log_path(params: ModelParameter) -> str:
    from ..utils import fs
    return fs.join(params.model_path, "DataLog.log")


def read_runs_log(params: ModelParameter) -> typing.List[dict]:
    from ..utils import fs
    path = runs_log_path(params)
    if not fs.exists(path):
        return []
    out = []
    with fs.open_(path) as f:
        for line in f:
            line = line.strip()
            if line:
                out.append(json.loads(line))
    return out


def append_runs_log(params: ModelParameter, steps: int, slice_count: int):
    """Record this run's data-consumption parameters
    (reference dataloader_placement.py:101-119)."""
    from ..utils import fs
    fs.makedirs(params.model_path)
    entry = {"steps": int(steps),
             "ctx": int(params.sequence_length),
             "slice_count": int(slice_count),
             "interleave_size": int(params.interleaved_datasets),
             "batch_size": int(params.train_batch_size),
             "grad_accumulation": int(params.grad_accumulation),
             "token_patch_size": int(params.token_patch_size)}
    with fs.open_(runs_log_path(params), "a") as f:
        f.write(json.dumps(entry) + "\n")
    return entry
