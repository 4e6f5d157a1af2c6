"""Build identity for scraped series and result files.

``hbnlp_build_info{git_rev,jax_version,backend,device_kind} 1`` is the
Prometheus build-info convention: a constant gauge whose LABELS carry the
identity, so any scraped series (and any ``telemetry.jsonl`` line) joins
back to the exact build that produced it.

Stdlib-only like the rest of the package, and it never STARTS a jax
backend: the backend fields are read only in a process that has already
initialised one (the trainer, a serving device loop).  A process that
merely imported jax — the router parent, the HTTP child — reports them
``unknown``: a chip belongs to one process, and asking
``jax.devices()`` here would take it from the replica that needs it.
The git rev is read once per process at first call, never on a hot path.
"""
from __future__ import annotations

import os
import subprocess
import sys
import typing

_BUILD_INFO: typing.Optional[typing.Dict[str, str]] = None

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _git_rev() -> str:
    try:
        out = subprocess.run(["git", "rev-parse", "--short=12", "HEAD"],
                             cwd=_REPO, capture_output=True, timeout=10)
        rev = out.stdout.decode().strip()
        if out.returncode == 0 and rev:
            return rev
    except Exception:
        pass
    return "unknown"


def _jax_version() -> str:
    mod = sys.modules.get("jax")
    if mod is not None:
        return getattr(mod, "__version__", "unknown")
    try:  # no jax in this process (HTTP child): metadata only, no import
        from importlib.metadata import version
        return version("jax")
    except Exception:
        return "unknown"


def build_info() -> typing.Dict[str, str]:
    """``{git_rev, jax_version, backend, device_kind}`` — computed once per
    process and cached.  Backend fields stay ``unknown`` unless this
    process has ALREADY initialised a jax backend (never triggers an init
    of its own — see the module docstring)."""
    global _BUILD_INFO
    if _BUILD_INFO is not None:
        return _BUILD_INFO
    backend = device_kind = "unknown"
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is not None and bridge.backends_are_initialized():
        mod = sys.modules["jax"]
        backend = mod.default_backend()
        device_kind = mod.devices()[0].device_kind
    _BUILD_INFO = {"git_rev": _git_rev(), "jax_version": _jax_version(),
                   "backend": backend, "device_kind": device_kind}
    return _BUILD_INFO


def register_build_info(reg=None) -> typing.Dict[str, str]:
    """Set the ``hbnlp_build_info`` gauge (value 1) in ``reg`` (default:
    the process registry) and return the info dict.  Idempotent; call once
    at startup of anything that exposes or dumps metrics."""
    from .registry import registry as _process_registry
    info = build_info()
    r = reg if reg is not None else _process_registry()
    r.gauge("hbnlp_build_info",
            "constant 1; build identity rides the labels",
            ("git_rev", "jax_version", "backend", "device_kind")
            ).labels(**info).set(1)
    return info
