#!/usr/bin/env python3
"""Record the small device trace the reduction is checked against.

Run once on the chip (``chiprun -- python benchmark/trace/record_fixture.py``):
three steps of a small jitted program — four bf16 matmuls, one elementwise
pass, the program's two Pallas kernels at a small shape — with host spans
(``jax.profiler.TraceAnnotation``) around the input stall and the dispatch,
and one deliberate host sleep outside any span.  Writes

  chiprun_out/trace_fixture/fixture.xplane.pb   the trace (copy it to
                                                benchmark/trace/fixtures/)
  chiprun_out/trace_fixture/structure.txt       planes, lines, event names
                                                and stat keys, to read by hand
  chiprun_out/trace_fixture/fence.json          does block_until_ready fence?

It never runs inside a benchmark run and nothing imports it.
"""
import glob
import json
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
OUT = os.path.join(REPO, "chiprun_out", "trace_fixture")


def main() -> int:
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"record_fixture.py: needs a TPU, jax found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    from homebrewnlp_tpu.parallel.flash_attention import attention as flash
    from homebrewnlp_tpu.parallel.map_mixer import mix

    os.makedirs(OUT, exist_ok=True)
    print("device:", dev.platform, repr(dev.device_kind), len(jax.devices()))
    print("memory_stats:", dev.memory_stats())

    # ---- is block_until_ready a fence on this attachment? (ROADMAP S0 f)
    @jax.jit
    def burn(x):
        return jax.lax.fori_loop(0, 300, lambda _, a: jnp.tanh(a @ a) * 0.5, x)

    x = jnp.ones((4096, 4096), jnp.bfloat16) * 0.01
    burn(x).block_until_ready()
    fence = []
    for _ in range(3):
        t0 = time.monotonic()
        y = burn(x)
        t1 = time.monotonic()
        y.block_until_ready()
        t2 = time.monotonic()
        float(y[0, 0])
        t3 = time.monotonic()
        fence.append({"dispatch_s": t1 - t0, "block_until_ready_s": t2 - t0,
                      "float_after_block_s": t3 - t2})
    for _ in range(3):
        t0 = time.monotonic()
        y = burn(x)
        float(y[0, 0])
        fence.append({"float_only_s": time.monotonic() - t0})
    flops = 300 * 2 * 4096 ** 3
    print("fence:", json.dumps(fence))
    print(f"burn: {flops / 1e12:.2f} TFLOP; at 197 TFLOP/s "
          f"{flops / 197e12 * 1e3:.1f} ms")
    with open(os.path.join(OUT, "fence.json"), "w") as f:
        json.dump({"flops": flops, "runs": fence}, f, indent=1)

    # ---- the fixture program
    def fixture_step(a, w, bias, v, q):
        with jax.named_scope("matmuls"):
            for _ in range(4):
                a = (a @ w).astype(jnp.bfloat16)
        with jax.named_scope("elementwise"):
            a = jnp.tanh(a)
        m = mix(bias, v, causal=True)
        o = flash(q, q, q, scale=1.0, causal=True)
        return a, m, o

    step = jax.jit(fixture_step)
    a = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    w = jnp.ones((2048, 2048), jnp.bfloat16) * 0.001
    bias = jnp.ones((2, 512, 512), jnp.bfloat16) * 0.01
    v = jnp.ones((2, 512, 2, 128), jnp.bfloat16)
    q = jnp.ones((1, 512, 2, 128), jnp.bfloat16) * 0.1
    jax.block_until_ready(step(a, w, bias, v, q))

    trace_dir = os.path.join(OUT, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        out = None
        for i in range(3):
            with jax.profiler.TraceAnnotation("data_next", step=i):
                time.sleep(0.002)
            with jax.profiler.TraceAnnotation("dispatch", step=i):
                out = step(a, w, bias, v, q)
            if i == 1:
                jax.block_until_ready(out)
                time.sleep(0.01)      # a gap no span covers
        with jax.profiler.TraceAnnotation("fence"):
            jax.block_until_ready(out)
    jax.profiler.stop_trace()

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(OUT, "fixture.xplane.pb"))
    print("trace:", path, os.path.getsize(path), "bytes")

    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    lines_out = []
    for plane in data.planes:
        lines_out.append(f"PLANE {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            total = sum(e.duration_ns for e in events)
            lines_out.append(f"  LINE {line.name!r}: {len(events)} events, "
                             f"{total / 1e6:.3f} ms in events")
            seen = {}
            for e in events:
                seen.setdefault(e.name, []).append(e)
            for name, evs in list(seen.items())[:40]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in evs[0].stats}
                lines_out.append(
                    f"    {name[:160]!r} x{len(evs)} first start "
                    f"{evs[0].start_ns:.0f} dur {evs[0].duration_ns:.0f} "
                    f"stats {stats}")
    with open(os.path.join(OUT, "structure.txt"), "w") as f:
        f.write("\n".join(lines_out) + "\n")
    print("\n".join(lines_out[:60]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
