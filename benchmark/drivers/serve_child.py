#!/usr/bin/env python3
"""The server exactly as ``main.py --run_mode web_api`` starts it, plus one
thread that sleeps on a named pipe.

The chip belongs to the server's process, so only this process can read its
memory or trace it, and the program offers no hook for either.  The thread
blocks in ``open()`` on the pipe (no polling: it costs nothing until the
harness writes) and serves three commands, one JSON object a line:

  {"cmd": "device", "out": path}        jax's devices and their
                                        ``memory_stats()`` -> ``path``
  {"cmd": "trace_start", "dir": path}   ``jax.profiler.start_trace``
  {"cmd": "trace_stop", "out": path}    ``stop_trace``; ``path`` marks it done

Everything else is ``main.main()``: same argument parsing, same model load,
same engine, same HTTP child, same SIGTERM drain.
"""
import json
import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _write(path: str, doc: dict) -> None:
    with open(path + ".tmp", "w") as f:
        json.dump(doc, f)
    os.replace(path + ".tmp", path)


def _serve_commands(pipe: str) -> None:
    while True:
        with open(pipe) as f:            # blocks until the harness writes
            lines = f.read().splitlines()
        # the first command comes after /health answered, so the server has
        # long imported jax and holds the chip (importing it here at thread
        # start would race the main thread's own import)
        import jax
        for line in lines:
            cmd = json.loads(line)
            if cmd["cmd"] == "device":
                devices = jax.devices()
                _write(cmd["out"], {
                    "platform": devices[0].platform,
                    "kind": devices[0].device_kind, "count": len(devices),
                    "memory": [d.memory_stats() or {} for d in devices]})
            elif cmd["cmd"] == "trace_start":
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(cmd["dir"], profiler_options=opts)
            elif cmd["cmd"] == "trace_stop":
                jax.profiler.stop_trace()
                _write(cmd["out"], {"stopped": True})


def main() -> int:
    pipe, model = sys.argv[1], sys.argv[2]
    threading.Thread(target=_serve_commands, args=(pipe,), daemon=True,
                     name="bench-commands").start()
    sys.path.insert(0, ROOT)
    sys.argv = ["main.py", "--model", model, "--run_mode", "web_api"]
    import main as program
    return program.main()


if __name__ == "__main__":
    sys.exit(main())
