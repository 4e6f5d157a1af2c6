#!/usr/bin/env python3
"""Does the system still start on the chip?  The quickest end-to-end proof.

Drives the main path once, through the entry points a user calls, at the
full width of the flagship (``configs/32big_mixer.json``: d4096 = 8 heads x
512, depth 32, seq 512, bf16, revnet) with only the deployment share changed
(batch 1024 over 32 cores -> 32 per data-parallel chip, ``tpu_size`` -> the
chips present, steps, paths; ``save_graph`` on so the trainer reports what it
compiled; the continuous engine pinned so a serving fallback is a failure):

  kernels  scripts/kernel_parity.py — compiled flash, map-mixer,
           delta-solve, chunked-scan (layer mamba) and both chunked delta
           rules' (layers gated_delta and kda) kernels against their XLA
           references at float32 "highest", and the windowed flash
           forward's two forms against float64
  train    main.py --run_mode train, 10 steps on TFRecords written by
           scripts/text2records.py from a seeded corpus; writes a checkpoint
  resume   a SECOND process restores it and trains 10 more steps; must add
           no train-step entry to the compile cache
  serve    main.py --run_mode web_api loads that checkpoint; /health must
           name the continuous engine; 8 greedy /token_completion requests
           of mixed prompt lengths in flight together, a repeated request
           must return the same tokens; SIGTERM must exit 0, nothing left

This parent never imports jax (asserted at exit): a chip belongs to one
process, so each leg is its own child, one at a time.  With no TPU it exits
non-zero within seconds and runs nothing.  ``--rehearse-cpu`` runs the same
sequence at a toy size on the CPU to exercise THIS file's logic; a rehearsal
never prints PASS, never prints the result line, and never exits 0.

Writes the dataset, configs and checkpoints under ``chip_smoke_out/`` and
its logs and ``report.json`` under ``chiprun_out/chip_smoke/`` (both
git-ignored).  The compile cache is wherever ``utils/compile_cache.py`` puts
it: ``JAX_COMPILATION_CACHE_DIR`` if set, else ``.jax_cache/`` here.

Last line of stdout on success, and only then:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
"""
import argparse
import ast
import json
import math
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(REPO, "chip_smoke_out")
REPORT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
NEEDS = ("main.py", "homebrewnlp_tpu", "configs/32big_mixer.json",
         "scripts/text2records.py", "scripts/kernel_parity.py")

#: the whole run must finish inside the driver's 1200 s
DEADLINE_S = 1150.0
#: exit code of a rehearsal whose legs all ran — never 0, which is a PASS
REHEARSAL_EXIT = 10
PORT = 62220  # infer/rest_api.py DEFAULT_PORT (main.py serves there)
TRAIN_STEPS = 10   # the train loop logs its metrics every 10th step
CORPUS_BYTES = 4 << 20
SEED = 20260926

#: what a rehearsal shrinks (the chip run changes none of these)
REHEARSAL_SHAPE = {"depth": 2, "features_per_head": 32, "heads": 4,
                   "sequence_length": 128, "train_batch_size": 8}

_PROBE = """
import json, jax
from homebrewnlp_tpu.utils.compile_cache import install_compile_cache
cache = install_compile_cache()
d = jax.devices()
try:
    import libtpu
    lt = libtpu.__version__
except ImportError:
    lt = None
print(json.dumps({"platform": d[0].platform, "kind": d[0].device_kind,
                  "count": len(d), "jax": jax.__version__, "libtpu": lt,
                  "cache_dir": cache}))
"""


class LegFailed(Exception):
    pass


_children = []
_t_start = time.monotonic()


def say(msg=""):
    print(msg, flush=True)


def remaining():
    return DEADLINE_S - (time.monotonic() - _t_start)


def check(cond, why):
    if not cond:
        raise LegFailed(why)


def spawn(cmd, log_path):
    """Start a child in its own session (so everything IT starts can be
    found and stopped), stdout+stderr to ``log_path``."""
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=REPO, stdout=log,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)
    _children.append(proc)
    return proc


def session_members(sid):
    """Live (non-zombie) pids whose session is ``sid``."""
    out = []
    for pid in os.listdir("/proc"):
        if not pid.isdigit():
            continue
        try:
            with open(f"/proc/{pid}/stat") as f:
                stat = f.read()
        except OSError:
            continue
        state, _ppid, _pgrp, session = stat[stat.rindex(")") + 2:].split()[:4]
        if int(session) == sid and state != "Z":
            out.append(int(pid))
    return out


def stop_session(proc):
    """Kill whatever is left of a child's session."""
    for pid in session_members(proc.pid):
        try:
            os.kill(pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    try:
        proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        pass


def run_to_end(name, cmd, log_path, timeout):
    """Run one child to completion; returns (rc, wall_s).  A child that
    outlives ``timeout`` (or the global deadline) is killed and fails."""
    timeout = min(timeout, remaining())
    check(timeout > 0, f"{name}: no time left in the {DEADLINE_S:.0f}s budget")
    t0 = time.monotonic()
    proc = spawn(cmd, log_path)
    try:
        rc = proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        stop_session(proc)
        raise LegFailed(f"{name}: still running after {timeout:.0f}s "
                        f"(log {log_path})")
    stop_session(proc)
    return rc, time.monotonic() - t0


def tail(path, n=25):
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""


def cache_entries(cache_dir, prefix=""):
    try:
        return sum(1 for f in os.listdir(cache_dir)
                   if f.endswith("-cache") and f.startswith(prefix))
    except OSError:
        return 0


# ---- inputs: config, corpus, records ---------------------------------------

def derive_configs(chips, rehearsal):
    """(train, resume) configs: the shipped flagship with the deployment
    share changed.  ``model`` parallelism 2 when the chips allow it, the
    rest data parallel with the reference's 32 sequences per data shard."""
    with open(os.path.join(REPO, "configs", "32big_mixer.json")) as f:
        cfg = json.load(f)
    model_par = 2 if chips > 1 and chips % 2 == 0 else 1
    data_par = chips // model_par
    cfg.update(
        train_batch_size=32 * data_par, tpu_size=chips,
        train_steps=TRAIN_STEPS, steps_per_checkpoint=1_000_000,
        model_path=os.path.join(WORK, "run"),
        dataset_configs=[{"path": os.path.join(WORK, "data", "*"),
                          "type": "text", "weight": 1}],
        # reporting and pinning, not tuning: the trainer writes and
        # summarises the executable it compiled; a server that cannot
        # build the continuous engine fails instead of degrading; a
        # request may wait out a cold engine compile
        save_graph=True, serve_engine="continuous",
        serve_request_deadline_s=900.0)
    if chips > 1:
        cfg["mesh_shape_override"] = {"data": data_par, "model": model_par}
    if rehearsal:
        cfg.update(REHEARSAL_SHAPE)
        cfg["train_batch_size"] = REHEARSAL_SHAPE["train_batch_size"] * data_par
    return cfg, dict(cfg, train_steps=2 * TRAIN_STEPS)


def write_corpus(path, seed=SEED, size=CORPUS_BYTES):
    """Seeded pseudo-text: a Zipf-weighted vocabulary of made-up words in
    sentences — byte statistics a char-level model can learn from, no
    network, identical on every machine."""
    rng = random.Random(seed)
    letters = "etaoinshrdlcumwfgypbvkjxqz"
    words = ["".join(rng.choices(letters, weights=range(26, 0, -1),
                                 k=rng.randint(2, 9))) for _ in range(4096)]
    weights = [1.0 / (i + 1) for i in range(len(words))]
    with open(path, "w") as f:
        written = 0
        while written < size:
            sentence = " ".join(rng.choices(words, weights=weights,
                                            k=rng.randint(4, 18)))
            line = sentence.capitalize() + rng.choice(".,.?!.") + \
                rng.choice(" \n")
            f.write(line)
            written += len(line)


def prepare_inputs(chips, rehearsal):
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(os.path.join(WORK, "data"))
    os.makedirs(REPORT_DIR, exist_ok=True)
    train_cfg, resume_cfg = derive_configs(chips, rehearsal)
    for name, cfg in (("train.json", train_cfg), ("resume.json", resume_cfg)):
        with open(os.path.join(WORK, name), "w") as f:
            json.dump(cfg, f, indent=1)
    corpus = os.path.join(WORK, "corpus.txt")
    write_corpus(corpus)
    rc, _ = run_to_end(
        "text2records", [sys.executable, "scripts/text2records.py", corpus,
                         "--output-dir", os.path.join(WORK, "data"),
                         "--prefix", "smoke"],
        os.path.join(REPORT_DIR, "text2records.log"), 300)
    records = os.listdir(os.path.join(WORK, "data"))
    check(rc == 0 and records, "text2records wrote no records:\n"
          + tail(os.path.join(REPORT_DIR, "text2records.log")))
    return train_cfg


# ---- legs ------------------------------------------------------------------

def leg_kernels(ctx):
    log = os.path.join(REPORT_DIR, "kernels.log")
    cmd = [sys.executable, "scripts/kernel_parity.py"]
    if ctx["rehearsal"]:
        cmd += ["--flash-seq", "256", "--mixer-batch", "2",
                "--solve-chunks", "2", "--band-heads", "1",
                "--band-seq", "1024", "--scan-seq", "512",
                "--rule-seq", "256", "--kda-rule-seq", "256"]
    rc, wall = run_to_end("kernels", cmd, log, 720)
    rows = []
    with open(log, errors="replace") as f:
        for line in f:
            if line.startswith('{"kernel"'):
                rows.append(json.loads(line))
    check(rc == 0 and len(rows) == 7,
          f"kernel parity failed (rc {rc}):\n{tail(log)}")
    if not ctx["rehearsal"]:
        check(all(r["implementation"] == "pallas" for r in rows),
              f"a dispatcher took the dense path on the chip: {rows}")
    return {"wall_s": wall,
            "implementation": {r["kernel"]: r["implementation"]
                               for r in rows},
            "max_err_over_max_ref": {r["kernel"]: r["max_err_over_max_ref"]
                                     for r in rows},
            "tolerance": rows[0]["tolerance"]}


def _result_field(log_text, key):
    m = re.search(rf"'{key}': ([-+0-9.eE]+|nan|inf)", log_text)
    return float(m.group(1)) if m else None


def _train_child(ctx, name, config, expect_step, restored_from):
    log = os.path.join(REPORT_DIR, f"{name}.log")
    cache = ctx["device"]["cache_dir"]
    before = cache_entries(cache), cache_entries(cache, "jit_step_fn")
    rc, wall = run_to_end(
        name, [sys.executable, "main.py", "--model",
               os.path.join(WORK, config), "--run_mode", "train"], log, 700)
    text = open(log, errors="replace").read()
    check(rc == 0, f"{name}: main.py exited {rc}:\n{tail(log)}")
    after = cache_entries(cache), cache_entries(cache, "jit_step_fn")
    out = {"wall_s": wall, "cache_entries": [before[0], after[0]],
           "train_step_cache_entries": [before[1], after[1]]}

    m = re.search(r"devices: platform=(\S+) kind='([^']*)' count=(\d+)"
                  r".* hbm_bytes=(\d+) \(([^)]*)\)", text)
    check(m, f"{name}: no 'devices:' line in {log}")
    out["device"] = {"platform": m.group(1), "kind": m.group(2),
                     "count": int(m.group(3)), "hbm_bytes": int(m.group(4)),
                     "hbm_source": m.group(5)}
    check(out["device"]["platform"] == ctx["device"]["platform"]
          and out["device"]["count"] == ctx["device"]["count"],
          f"{name} ran on {out['device']}, the probe saw {ctx['device']}")
    if restored_from:
        check(f"restored checkpoint at step {restored_from}" in text,
              f"{name}: did not restore step {restored_from}")

    # which implementation it took: the compiled step's own kernel census,
    # and no decline line from model/spatial.py
    m = re.search(r"kernels in the executable: (\{.*\})", text)
    check(m, f"{name}: no save_graph executable summary in {log}")
    out["executable_custom_calls"] = ast.literal_eval(m.group(1))
    check("kernel fallback" not in text,
          f"{name}: a kernel declined — see 'kernel fallback' in {log}")
    m = re.search(r"record reader: (.*)", text)
    out["record_reader"] = m.group(1) if m else None

    m = re.search(r"placement: mesh=(.*?) parameter shards on (\d+)/(\d+) "
                  r"local devices; bytes_in_use=(\{.*\})", text)
    check(m, f"{name}: no 'placement:' line in {log}")
    in_use = ast.literal_eval(m.group(4))
    out["mesh"] = ast.literal_eval(m.group(1))
    out["bytes_in_use"] = in_use
    check(int(m.group(2)) == int(m.group(3)) == ctx["device"]["count"],
          f"{name}: parameters on {m.group(2)} of {m.group(3)} devices")
    if not ctx["rehearsal"]:
        check(out["executable_custom_calls"].get("tpu_custom_call", 0) > 0,
              f"{name}: no tpu_custom_call in the compiled train step")
        check(all(v and v > 0 for v in in_use.values()),
              f"{name}: a device reports no memory in use: {in_use}")
        check(out["record_reader"] and out["record_reader"].startswith(
            "native"), f"{name}: record reader {out['record_reader']!r}")
    if ctx["device"]["count"] > 1:
        check(out["mesh"] == ctx["train_cfg"]["mesh_shape_override"],
              f"{name}: mesh {out['mesh']}, asked for "
              f"{ctx['train_cfg']['mesh_shape_override']}")

    for key in ("setup_s", "compile_s", "wall_s"):
        out[f"child_{key}"] = _result_field(text, key)
    check(_result_field(text, "final_step") == expect_step,
          f"{name}: final_step {_result_field(text, 'final_step')}, "
          f"expected {expect_step}")
    loss = None
    with open(os.path.join(WORK, "run", "metrics.jsonl")) as f:
        for line in f:
            row = json.loads(line)
            if row.get("step") == expect_step:
                loss = row.get("loss")
    check(loss is not None and math.isfinite(loss),
          f"{name}: loss at step {expect_step} is {loss}")
    out["loss"] = loss
    check(os.path.exists(os.path.join(WORK, "run", f"ckpt_{expect_step}",
                                      "index.json")),
          f"{name}: no checkpoint ckpt_{expect_step}")
    return out


def leg_train(ctx):
    out = _train_child(ctx, "train", "train.json", TRAIN_STEPS, None)
    before, after = out["train_step_cache_entries"]
    check(after - before <= 1,
          f"train: {after - before} train-step executables were compiled; "
          "one program should serve every step")
    return out


def leg_resume(ctx):
    out = _train_child(ctx, "resume", "resume.json", 2 * TRAIN_STEPS,
                       TRAIN_STEPS)
    before, after = out["train_step_cache_entries"]
    check(before > 0 and after == before,
          f"resume: train-step cache entries went {before} -> {after}; the "
          "second process should have found the first one's executable")
    return out


def _http(method, path, body=None, timeout=30.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{PORT}{path}", data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _complete(tokens, max_tokens, timeout):
    return _http("POST", "/token_completion",
                 {"tokens": tokens, "max_tokens": max_tokens,
                  "temperature": 0.0, "timeout_s": timeout},
                 timeout=timeout + 10)["tokens"]


def leg_serve(ctx):
    log = os.path.join(REPORT_DIR, "serve.log")
    cache = ctx["device"]["cache_dir"]
    entries_before = cache_entries(cache)
    seq = ctx["train_cfg"]["sequence_length"]
    t0 = time.monotonic()
    proc = spawn([sys.executable, "main.py", "--model",
                  os.path.join(WORK, "resume.json"), "--run_mode", "web_api"],
                 log)
    try:
        # -- set-up: until the HTTP child answers /health
        health = None
        while health is None:
            check(proc.poll() is None,
                  f"serve: main.py exited {proc.returncode} before serving:\n"
                  + tail(log))
            check(time.monotonic() - t0 < min(600, remaining()),
                  f"serve: not healthy after {time.monotonic() - t0:.0f}s:\n"
                  + tail(log))
            try:
                health = _http("GET", "/health", timeout=5)
            except (urllib.error.URLError, OSError, ValueError):
                time.sleep(1.0)
        setup_s = time.monotonic() - t0
        engine = health.get("engine") or {}
        check(health.get("status") == "ok"
              and engine.get("mode") == "continuous"
              and engine.get("program") == "engine_chunk_step",
              f"serve: /health does not name the continuous engine: {health}")
        text = open(log, errors="replace").read()
        check(f"loaded checkpoint at step {2 * TRAIN_STEPS}" in text,
              "serve: did not load the resumed checkpoint")

        # -- compile: the engine builds its three chunk programs on first
        # use — a request spanning two chunks (init, then plain), then one
        # admitted into the live pool (admit)
        rng = random.Random(SEED)
        prompt = lambda n: [rng.randrange(256) for _ in range(n)]  # noqa: E731
        t1 = time.monotonic()
        budget = max(30.0, min(700.0, remaining() - 90))
        _complete(prompt(8), min(80, seq - 16), budget)
        _complete(prompt(5), 4, budget)
        compile_s = time.monotonic() - t1

        # -- run: eight greedy requests of mixed prompt lengths, all in
        # flight together; the second and the last are the same request
        lengths = [3, 17, 64, 129, 200, 33, 300]
        prompts = [prompt(min(n, seq - 40)) for n in lengths]
        prompts.append(list(prompts[1]))
        results = [None] * len(prompts)
        spans = [None] * len(prompts)
        new_tokens = 24

        def one(i):
            start = time.monotonic()
            try:
                results[i] = _complete(prompts[i], new_tokens, 240.0)
            except Exception as exc:  # noqa: BLE001 — reported below
                results[i] = exc
            spans[i] = (start, time.monotonic())

        t2 = time.monotonic()
        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        check(not any(t.is_alive() for t in threads),
              "serve: a request never returned")
        run_s = time.monotonic() - t2
        for i, (p, r) in enumerate(zip(prompts, results)):
            check(isinstance(r, list), f"serve: request {i} failed: {r!r}")
            check(len(r) == len(p) + new_tokens and r[:len(p)] == p
                  and all(isinstance(t, int) and 0 <= t < 256 for t in r),
                  f"serve: request {i} (prompt {len(p)}) returned {r}")
        in_flight = max(sum(1 for s, e in spans if s <= t <= e)
                        for t, _ in spans)
        check(in_flight >= 3, f"serve: only {in_flight} requests overlapped")
        check(results[1] == results[-1],
              "serve: the same greedy request returned different tokens "
              "within one batch")
        again = _complete(prompts[1], new_tokens, 240.0)
        check(again == results[1], "serve: the same greedy request returned "
              "different tokens when repeated alone")
        after = _http("GET", "/health", timeout=5)
        check(after.get("status") == "ok" and after.get("breaker") == "closed"
              and not after.get("decode_failures"),
              f"serve: unhealthy after the requests: {after}")

        # -- SIGTERM drains and exits 0, and nothing of its session stays
        t3 = time.monotonic()
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=90)
        except subprocess.TimeoutExpired:
            raise LegFailed("serve: still alive 90s after SIGTERM")
        check(rc == 0, f"serve: exit code {rc} after SIGTERM:\n{tail(log)}")
        left = session_members(proc.pid)
        for _ in range(20):
            if not left:
                break
            time.sleep(0.5)
            left = session_members(proc.pid)
        check(not left, f"serve: processes left behind after exit: {left}")
        placement = re.search(r"placement: mesh=(.*?) parameter shards on "
                              r"(\d+)/(\d+)", open(log, errors="replace").read())
        check(placement and int(placement.group(2))
              == int(placement.group(3)) == ctx["device"]["count"],
              "serve: parameters do not cover every device")
        return {"wall_s": time.monotonic() - t0, "setup_s": setup_s,
                "compile_s": compile_s, "run_s": run_s,
                "drain_s": time.monotonic() - t3,
                "mesh": ast.literal_eval(placement.group(1)),
                "engine": engine, "requests": len(prompts) + 3,
                "max_in_flight": in_flight,
                "cache_entries": [entries_before, cache_entries(cache)]}
    finally:
        stop_session(proc)


LEGS = (("kernels", leg_kernels), ("train", leg_train),
        ("resume", leg_resume), ("serve", leg_serve))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run the sequence at a toy size on the CPU to "
                         "exercise this script; can never pass")
    args = ap.parse_args(argv)
    missing = [n for n in NEEDS if not os.path.exists(os.path.join(REPO, n))]
    if missing:
        print(f"chip_smoke.py: not a checkout of the repo — missing "
              f"{missing}", file=sys.stderr)
        return 2

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # every child inherits it
    try:
        probe = subprocess.run([sys.executable, "-c", _PROBE], cwd=REPO,
                               capture_output=True, text=True, timeout=300)
    except subprocess.TimeoutExpired:
        print("chip_smoke.py: the device probe hung for 300s",
              file=sys.stderr)
        return 2
    if probe.returncode != 0:
        print("chip_smoke.py: jax could not start a backend:\n"
              + probe.stderr[-2000:], file=sys.stderr)
        return 2
    device = json.loads(probe.stdout.strip().splitlines()[-1])
    if device["platform"] != "tpu" and not args.rehearse_cpu:
        print(f"chip_smoke.py: no TPU — jax found platform "
              f"{device['platform']!r} ({device['kind']} x{device['count']}); "
              "nothing was run (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r})", file=sys.stderr)
        return 2
    rehearsal = device["platform"] != "tpu"
    say(f"device: platform={device['platform']} kind={device['kind']!r} "
        f"count={device['count']} jax={device['jax']} "
        f"libtpu={device['libtpu']}")
    say(f"compile cache: {device['cache_dir']} "
        f"({cache_entries(device['cache_dir'])} entries; "
        f"JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    if rehearsal:
        say("REHEARSAL on the CPU at a toy size: exercises chip_smoke.py, "
            "proves nothing about the chip")

    ctx = {"device": device, "rehearsal": rehearsal}
    report = {"device": device, "rehearsal": rehearsal, "legs": {}}
    failed = []
    try:
        ctx["train_cfg"] = prepare_inputs(device["count"], rehearsal)
        for name, leg in LEGS:
            if any(f != "kernels" for f in failed):
                # resume needs train's checkpoint, serve needs resume's
                report["legs"][name] = {"status": "skipped"}
                say(f"leg {name}: skipped (an earlier leg failed)")
                continue
            t0 = time.monotonic()
            try:
                out = leg(ctx)
                out["status"] = "ok"
            except LegFailed as exc:
                out = {"status": "failed", "why": str(exc),
                       "wall_s": time.monotonic() - t0}
                failed.append(name)
            report["legs"][name] = out
            word = ("FAIL" if out["status"] == "failed"
                    else "ran (rehearsal)" if rehearsal else "PASS")
            say(f"leg {name}: {word} — " + json.dumps(
                {k: (float(f"{v:.4g}") if isinstance(v, float) else v)
                 for k, v in out.items() if k != "status"}))
    except LegFailed as exc:
        failed.append("inputs")
        say(f"inputs: FAIL — {exc}")
    finally:
        for proc in _children:
            stop_session(proc)
        report["wall_s"] = time.monotonic() - _t_start
        report["failed"] = failed
        os.makedirs(REPORT_DIR, exist_ok=True)
        with open(os.path.join(REPORT_DIR, "report.json"), "w") as f:
            json.dump(report, f, indent=1)

    train, resume = (report["legs"].get(n, {}) for n in ("train", "resume"))
    if train.get("status") == resume.get("status") == "ok":
        say(f"cold vs warm: train wall {train['wall_s']:.1f}s (compile "
            f"{train['child_compile_s']:.1f}s) / resume wall "
            f"{resume['wall_s']:.1f}s (compile "
            f"{resume['child_compile_s']:.1f}s)")
    say(f"total wall {report['wall_s']:.1f}s of {DEADLINE_S:.0f}s")
    assert "jax" not in sys.modules, "the smoke parent must stay off jax"
    if failed:
        say(f"FAILED legs: {failed}")
        return 1
    if rehearsal:
        say("rehearsal complete: every leg ran; this is not a pass")
        return REHEARSAL_EXIT
    say("PASS")
    say(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
