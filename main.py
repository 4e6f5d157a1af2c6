#!/usr/bin/env python3
"""CLI entry point.

Mirrors the reference CLI (/root/reference/main.py:17-22):
  python3 main.py --model configs/foo.json --run_mode {train,sample,query,web_api,debug}
``--tpu``/``--workers``/``--debug_grad`` are accepted for drop-in
compatibility (TPU connection is implicit through jax; no TF1 session).
"""
import argparse
import json
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", type=str, default="config.json",
                    help="path to the model config JSON")
    ap.add_argument("--tpu", type=str, default="",
                    help="accepted for compatibility; jax discovers devices")
    ap.add_argument("--workers", type=int, default=None,
                    help="REST worker count; overrides web_workers in the "
                         "config only when given explicitly")
    ap.add_argument("--run_mode", type=str, default="train",
                    choices=["train", "sample", "query", "web_api", "debug",
                             "debug_old", "analyze"])
    ap.add_argument("--debug_grad", action="store_true")
    args = ap.parse_args()

    # multi-host: explicit HBNLP_* flags (the CPU multiprocess rig /
    # run_manager --num-processes) or the standard env / TPU pod metadata
    # (the reference resolved a TPUClusterResolver here, src/main.py:107-117).
    # Single-process runs skip this entirely (docs/DISTRIBUTED.md).
    from homebrewnlp_tpu.distributed import bootstrap as dist_bootstrap
    dist_bootstrap.maybe_initialize()

    with open(args.model) as f:
        config = json.load(f)

    from homebrewnlp_tpu.config import ModelParameter
    from homebrewnlp_tpu.run.modes import RUN_MODE_FNS
    from homebrewnlp_tpu.train import checkpoint as ckpt
    from homebrewnlp_tpu.utils import retry

    params = ModelParameter(config)
    # persistent XLA compile cache applies to EVERY run mode and must be
    # configured before the first jit compile: warm restarts (run_manager
    # relaunches, serving respawns) then skip the compile+warmup tax
    from homebrewnlp_tpu.utils.compile_cache import install_compile_cache
    print(f"persistent compilation cache: {install_compile_cache()}")
    # storage retry knobs apply to EVERY run mode (serving restores through
    # the same flaky bucket as training; train() re-installs identically)
    retry.set_default_policy(retry.RetryPolicy(
        max_attempts=params.storage_retry_attempts,
        base_delay=params.storage_retry_base_delay))
    params.debug_gradients = args.debug_grad
    # CLI --workers overrides the config (reference src/main.py:60) — but
    # only when actually passed, so web_workers in the JSON stays effective
    if args.workers is not None:
        params.web_workers = args.workers
    params.train = args.run_mode == "train"
    if not params.use_autoregressive_sampling and args.run_mode in ("sample",):
        print("use_autoregressive_sampling is off; enabling for sample mode")
        params.use_autoregressive_sampling = True
    params.current_step = ckpt.latest_step(params.model_path)

    # train_mode returns PREEMPTED_EXIT_CODE (143) after a SIGTERM-triggered
    # emergency checkpoint so supervisors relaunch instead of finishing
    try:
        rc = RUN_MODE_FNS[args.run_mode](params, args)
    finally:
        # clean disconnect from the coordinator — including on the
        # preemption path, so peers fail their next barrier with a named
        # error instead of a gRPC reset (no-op unless bootstrap initialized)
        dist_bootstrap.shutdown()
    return int(rc) if rc else 0


if __name__ == "__main__":
    sys.exit(main())
