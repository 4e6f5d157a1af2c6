#!/usr/bin/env python3
"""Headline benchmark: LM tokens/sec/chip on the 32big_mixer recipe.

Prints the headline JSON line {"metric", "value", "unit", "vs_baseline"}
first, then (on success) ONE enriched line adding the long-context
companion keys — consumers should take the LAST line; the early headline
only survives alone if the companion's 16k compile kills the process.

The architecture matches configs/32big_mixer.json of the reference
(/root/reference/configs/32big_mixer.json: seq 512, 8 heads x 512
features/head = d4096, depth 32 x 2 block parts, char vocab 256, bf16,
revnet, adaptive_clip-sm3-momentum-learning_rate); the per-chip batch is
sized for one chip (the reference ran batch 1024 across a 32-core pod =
32/chip; we use 32/chip).  The reference publishes no numbers
(BASELINE.md), so vs_baseline is tracked against the first recorded run of
this benchmark (BENCH_BASELINE.json), giving round-over-round progress.
"""
import argparse
import json
import os
import sys
import time

BASELINE_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "BENCH_BASELINE.json")

#: ``--check``: the measured headline may drop at most this fraction below
#: the committed per-backend floor before the gate fails (same banding idea
#: as the cost-ledger tolerance: run-to-run noise on shared rigs is real,
#: a structural regression is much larger)
CHECK_TOLERANCE = 0.10

BENCH_CONFIG = {
    "model_mode": "gpt", "use_video": False, "use_language": True,
    "sequence_length": 512, "features_per_head": 512, "heads": 8, "depth": 32,
    "train_batch_size": 32, "vocab_size": 256,
    "calc_accuracy": False, "memory_reduction_strategy": "revnet",
    "block_config": [
        {"layer": ["norm-shift-scale-features-group",
                   "bottleneck_group_linear-in:relu-mid:relu-mid:norm-mid:shift-mid:scale-mid:features"]},
        {"layer": ["norm-shift-scale-features-group",
                   "attention-biased_attention_map-absolute-input_as_value-shared",
                   "norm-shift-scale-features-group", "activation-gelu",
                   "attention-biased_attention_map-absolute-input_as_value-shared"]}],
    "group_linear_factor": 2,
    "intermediate_feed_forward_multiplier_multiplier": 0.5,
    "optimizer": "adaptive_clip:0.003-sm3-momentum:0.9:1:1-learning_rate",
    "learning_rate": 0.01, "weight_decay": 0.0001,
    "learning_rate_config": {"linear_warmup": {"final_step": 4096}},
    "calculation_dtype": "bfloat16", "storage_dtype": "bfloat16",
    "optimizer_slice_dtype": "bfloat16", "slice_dtype": "float32",
    "scale_by_depth": True, "embedding_stddev": 0.004, "z_loss": 1e-4,
    "use_checkpointing": False, "macro_batching": 1,
    "model_path": "/tmp/bench_run",
}

WARMUP_STEPS = 2
MEASURE_STEPS = 10
#: instrumented steps for the phase-attribution companion (run AFTER the
#: headline measurement so its per-step device sync can't touch the number)
PHASE_STEPS = 5


def _require_accelerator():
    """This benchmark measures the chip: with no accelerator it fails (exit
    code 2) instead of producing a CPU number under a device metric's
    name."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        print("bench.py: jax found no accelerator (platform 'cpu') — "
              "refusing to benchmark on the CPU", file=sys.stderr)
        raise SystemExit(2)
    return dev


def check_floor(value: float, backend: str) -> int:
    """``--check``: nonzero when the measured headline tokens/sec/chip
    falls below the committed per-backend floor minus the tolerance band
    (BENCH_BASELINE.json ``floor`` keys; mirrors ``bench_serving.py
    --check``).  No committed floor for this backend = loud failure, not a
    vacuous pass."""
    try:
        with open(BASELINE_FILE) as f:
            baselines = json.load(f)
    except (OSError, ValueError) as exc:
        print(f"--check: cannot read {BASELINE_FILE}: {exc}",
              file=sys.stderr)
        return 1
    floor = (baselines.get(backend) or {}).get("floor")
    if not floor:
        print(f"--check: no committed floor for backend {backend!r} in "
              f"{BASELINE_FILE} — commit one from a healthy run",
              file=sys.stderr)
        return 1
    limit = float(floor) * (1.0 - CHECK_TOLERANCE)
    verdict = "PASS" if value >= limit else "FAIL"
    print(f"--check [{verdict}]: {value:.0f} tokens/sec/chip vs floor "
          f"{float(floor):.0f} (-{CHECK_TOLERANCE:.0%} band = {limit:.0f}, "
          f"backend {backend})", file=sys.stderr)
    return 0 if value >= limit else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--check", action="store_true",
                    help="exit nonzero when the flagship tokens/sec/chip "
                         "drops below the committed floor "
                         "(BENCH_BASELINE.json, tolerance-banded) — the "
                         "headline-perf regression gate")
    args = ap.parse_args(argv)
    import numpy as np
    t_setup = time.monotonic()
    import jax
    import jax.numpy as jnp
    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.model import Model
    from homebrewnlp_tpu.train import Trainer

    device = _require_accelerator()
    rng = np.random.default_rng(0)
    params = ModelParameter(dict(BENCH_CONFIG))
    trainer = Trainer(params, Model(params))

    def make_batch():
        x = rng.integers(0, params.vocab_size,
                         (params.train_batch_size, params.sequence_length, 1))
        return {"token_x": jnp.asarray(x),
                "token_y": jnp.asarray((x + 1) % params.vocab_size)}

    state = trainer.init_state(make_batch())
    print(f"setup {time.monotonic() - t_setup:.1f}s; compiling...",
          file=sys.stderr)
    t_compile = time.monotonic()
    for _ in range(WARMUP_STEPS):
        state, metrics = trainer.step(state, make_batch())
    float(metrics["loss"])  # value fetch = device sync
    print(f"compile+warmup {time.monotonic() - t_compile:.1f}s",
          file=sys.stderr)

    batches = [make_batch() for _ in range(MEASURE_STEPS)]
    t0 = time.monotonic()
    for batch in batches:
        state, metrics = trainer.step(state, batch)
    final_loss = float(metrics["loss"])  # value fetch = true device sync
    dt = time.monotonic() - t0

    # step-phase attribution (docs/OBSERVABILITY.md): a short instrumented
    # pass so BENCH_* files carry data-wait / dispatch / device-block
    # medians and prefetcher stall totals, not just the end-to-end number.
    # Runs on a PRIVATE registry after the headline loop — the per-step
    # sync it needs cannot contaminate the headline measurement.
    from homebrewnlp_tpu import telemetry
    from homebrewnlp_tpu.data.inputs import Prefetcher
    reg = telemetry.Registry()
    prev_reg = telemetry.set_registry(reg)
    try:
        data_wait, dispatch, device_block = (
            telemetry.Phase(f"train/{n}", reg)
            for n in ("data_wait", "dispatch", "device_block"))
        mono = time.monotonic
        feed = Prefetcher((make_batch() for _ in range(PHASE_STEPS)),
                          depth=2, telemetry_label="bench")
        try:
            for _ in range(PHASE_STEPS):
                tp0 = mono()
                b = next(feed)
                tp1 = mono()
                data_wait.rec(tp0, tp1 - tp0)
                state, pm = trainer.step(state, b)
                tp2 = mono()
                dispatch.rec(tp1, tp2 - tp1)
                float(pm["loss"])  # device sync attributes device time
                device_block.rec(tp2, mono() - tp2)
        finally:
            # a mid-pass failure must not leak the fill thread and its
            # pinned batches into the decode companion's memory budget
            feed.close()
        telemetry_summary = telemetry.summarize(reg.snapshot())
    finally:
        telemetry.set_registry(prev_reg)

    tokens = MEASURE_STEPS * params.train_batch_size * params.sequence_length
    n_chips = max(1, len(jax.devices()))
    tokens_per_sec_chip = tokens / dt / n_chips

    # val loss: the driver metric is tokens/sec/chip + VAL LOSS
    # (BASELINE.json); held-out batches from the same synthetic stream,
    # forward-only with dropout off (Trainer.eval_loss)
    val_losses = [float(trainer.eval_loss(state, make_batch())["loss"])
                  for _ in range(4)]
    val_loss = sum(val_losses) / len(val_losses)

    # MFU: exact matmul FLOPs from the jaxpr, 3x-forward convention (no
    # rematerialization credit — revnet's recompute is not "useful" FLOPs).
    # Dual convention: "mfu" counts causally-dead flash cells as useful
    # (full-square, stable round-over-round); "mfu_causal" excludes them
    # (the executed-FLOP denominator; emitted when the model has causal
    # flash kernels)
    from homebrewnlp_tpu.utils.flops import forward_flops_split, mfu
    fwd_flops, fwd_exec = forward_flops_split(
        lambda v, b: trainer.model.apply(v, b).total_loss.data,
        state.variables, batches[0])
    mfu_frac = mfu(fwd_flops, dt / MEASURE_STEPS, n_chips)
    mfu_causal = mfu(fwd_exec, dt / MEASURE_STEPS, n_chips)

    # collective census of the headline train step (docs/STATIC_ANALYSIS.md):
    # BENCH_*.json tracks comms growth round over round the same way it
    # tracks tokens/sec — an unexplained new collective kind in the trend is
    # accidental resharding.  Needs a second compile of the step (the
    # executed jit's compiled module is not retrievable), so it runs only
    # when BENCH_COLLECTIVES=1 asks for it.
    collectives = None
    if os.environ.get("BENCH_COLLECTIVES") == "1":
        from homebrewnlp_tpu.analysis import hlo_lint
        hlo = trainer.lowered(state, batches[0]).compile().as_text()
        collectives = hlo_lint.collective_census(hlo)

    # per-scope cost ledger of the headline step (docs/OBSERVABILITY.md
    # 'Cost attribution'): BENCH_*.json rows become self-attributing —
    # which block holds the FLOPs/bytes, and what each is bound by.  A
    # trace of the already-built step (no second compile);
    # BENCH_COST_LEDGER=1 asks for it.
    cost_ledger_tab = None
    if os.environ.get("BENCH_COST_LEDGER") == "1":
        from homebrewnlp_tpu.analysis import cost_ledger as cl
        from homebrewnlp_tpu.utils import flops as flops_mod
        traced = trainer._step_fn.trace(state, batches[0],
                                        jax.random.PRNGKey(0))
        # bench rows describe THIS device run: classify bounds against
        # the measured chip's ridge, not the committed ledger's fixed
        # reference chip (cost_ledger.ROOFLINE_DEVICE)
        cost_ledger_tab = cl.scope_table(
            traced.jaxpr, peak=flops_mod.peak_flops(device),
            bandwidth=flops_mod.peak_hbm_bandwidth(device))
        cost_ledger_tab["roofline_device"] = device.device_kind

    # progress against the committed first recorded value for this backend
    # (read-only: a benchmark run never rewrites a tracked file; batch size
    # is part of the config identity)
    vs_baseline = 1.0
    backend = device.platform
    config_id = f"32big_mixer/1chip/b{params.train_batch_size}"
    with open(BASELINE_FILE) as f:
        prior = json.load(f).get(backend, {})
    if prior.get("value") and prior.get("config", config_id) == config_id:
        vs_baseline = tokens_per_sec_chip / float(prior["value"])

    print(f"final loss {final_loss:.4f}", file=sys.stderr)
    out = {"metric": "LM tokens/sec/chip @ 32big_mixer",
           "value": round(tokens_per_sec_chip, 2),
           "unit": "tokens/sec/chip",
           "platform": device.platform, "device_kind": device.device_kind,
           "device_count": len(jax.devices()),
           "vs_baseline": round(vs_baseline, 4),
           # what vs_baseline compares against: the first recorded run of
           # THIS benchmark (round 1), not the MTF reference — the reference
           # publishes no single-chip numbers and pod hardware for a direct
           # loss/throughput comparison is unavailable (BASELINE.md)
           "baseline_ref": "round1 self-baseline (BENCH_BASELINE.json); "
                           "MTF comparison hardware-blocked"}
    out["mfu"] = round(mfu_frac, 4)
    if round(mfu_causal, 4) != round(mfu_frac, 4):
        out["mfu_causal"] = round(mfu_causal, 4)
    out["val_loss"] = round(val_loss, 4)
    out["telemetry"] = telemetry_summary
    if collectives is not None:
        out["collectives"] = collectives
    if cost_ledger_tab is not None:
        out["cost_ledger"] = cost_ledger_tab
    # the headline line goes out NOW: the companion's 16k compile can kill
    # the PROCESS (worker crash / OOM), which no except clause survives — a
    # consumer taking the last JSON line sees the enriched line when the
    # companion succeeds and this one when it dies
    print(json.dumps(out), flush=True)

    if args.check:
        # gate mode: the verdict is about the headline number only — skip
        # the companion benches so a CI gate pays one build, not five
        return check_floor(tokens_per_sec_chip, backend)

    failed = []

    def companion(label: str, prefix: str, run_fn, keys=(),
                  value_key: str = "value",
                  value_dst: str = "_tokens_per_sec_chip"):
        """Run one companion bench, merge its result under ``prefix`` onto
        the headline line, re-print the enriched line.  A companion that
        raises — or returns a dict WITHOUT ``value_key`` (e.g. an error
        dict) — is recorded in ``failed`` so the remaining companions still
        run, and the process then exits non-zero."""
        try:
            res = run_fn()
            if not isinstance(res, dict) or value_key not in res:
                raise KeyError(f"companion result has no {value_key!r}: "
                               f"{str(res)[:200]}")
        except Exception as exc:  # noqa: BLE001 — reported via exit code
            print(f"{label} companion bench failed: {exc!r}", file=sys.stderr)
            failed.append(label)
            return
        out[prefix + value_dst] = res[value_key]
        for key, dst in (("metric", f"{prefix}_metric"),
                         ("mfu", f"{prefix}_mfu"),
                         ("mfu_causal", f"{prefix}_mfu_causal"),
                         *keys):
            if key in res:
                out[dst] = res[key]
        print(json.dumps(out), flush=True)

    # long-context companion (seq 16,384): the flagship line alone would
    # hide the framework's long-context throughput (BASELINE.md 'Long
    # context')
    state = trainer = batches = None  # free HBM before the 16k compile
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "scripts"))
    import bench_long_context as lc
    companion("long-context", "long_context", lc.run)

    # 32k companion: the longest context one chip trains; the fused
    # backward admits its 4.3GB dq-partial buffer through the memory-aware
    # default cap — no env override needed
    companion("32k", "long_context_32k", lambda: lc.run(seq=32768))

    # routed-MoE companion: the EP component's standing throughput
    # number (configs/moe_mixer.json, BASELINE.md round 5)
    def run_moe():
        import bench_moe
        return bench_moe.run()
    companion("moe", "moe", run_moe,
              keys=(("expert_utilization_min_at_init",
                     "moe_expert_utilization_min_at_init"),))

    # decode-latency companion: the sequence-scaling probe as a TRACKED
    # metric — ms/token at 8k/16k/32k with bf16 and int8 caches, plus the
    # 32k/8k per-token-vs-byte ratio that caught the cache-carry copy bug
    # (BASELINE.md round 5)
    def run_decode():
        import bench_decode
        return bench_decode.run()
    companion("decode", "decode", run_decode,
              keys=(("rows", "decode_rows"),
                    ("scaling_ratio_large_small",
                     "decode_scaling_ratio_large_small"),
                    ("byte_ratio_large_small",
                     "decode_byte_ratio_large_small")),
              value_dst="_ms_per_token")
    if failed:
        print(f"bench.py: companion benches failed: {failed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
