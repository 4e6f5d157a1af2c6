"""Child processes: start one in its own session, find what it started,
stop all of it.  Copied from ``chip_smoke.py`` (``spawn``,
``session_members``, ``stop_session``, ``tail``)."""
from __future__ import annotations

import os
import signal
import subprocess
import time
import typing


def spawn(cmd: typing.Sequence[str], log_path: str, cwd: str,
          env: typing.Optional[dict] = None) -> subprocess.Popen:
    """Start a child in its own session (so everything IT starts can be
    found and stopped), stdout+stderr to ``log_path``."""
    with open(log_path, "w") as log:
        return subprocess.Popen(list(cmd), cwd=cwd, stdout=log, env=env,
                                stderr=subprocess.STDOUT,
                                start_new_session=True)


def session_members(sid: int) -> typing.List[int]:
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


def stop_session(proc: subprocess.Popen, grace_s: float = 30.0) -> int:
    """SIGTERM, wait, then SIGKILL whatever is left of the child's session;
    returns the child's exit code (or -9)."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass
    deadline = time.monotonic() + 10.0
    while session_members(proc.pid) and time.monotonic() < deadline:
        for pid in session_members(proc.pid):
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
        time.sleep(0.2)
    try:
        return proc.wait(timeout=10)
    except subprocess.TimeoutExpired:
        return -9


def tail(path: str, n: int = 25) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError:
        return ""
