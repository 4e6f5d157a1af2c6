"""Test env: force CPU with 8 virtual devices BEFORE jax initialises.

Mirrors the reference's PlacementMeshImpl-on-cpu:0 test harness
(/root/reference/tests/backend.py:45-59) but with a real 8-device mesh so
NamedSharding layouts and collectives are exercised (SURVEY.md §4 notes the
reference never tests multi-core behavior; we do).
"""
import os

# jax is not yet imported when this module loads, so in-process env
# mutation suffices
flags = os.environ.get("XLA_FLAGS", "")
os.environ["JAX_PLATFORMS"] = "cpu"
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
# PR 74: a toy program's seconds are LLVM's, not its own.  With jax's own
# ``jax_disable_most_optimizations`` (XLA:CPU's backend level 0) the same
# tests compile for two thirds of the CPU-seconds (tests/kimi_linear_test.py
# alone: 369 -> 254) and run as long as before; a test that needs LLVM's
# whole pipeline says so (``optimised`` below).  What is compiled for a
# described v5e is libtpu's own and reads no such flag
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "optimised: compile this test's programs with XLA:CPU's "
        "whole pipeline: it holds two programs to the same BITS, or an "
        "interpreted kernel to its XLA form inside one rounding, and the "
        "unoptimised code of two programs contracts and vectorises "
        "differently")


@pytest.fixture(autouse=True)
def _backend_level(request):
    """A test marked ``optimised`` runs with ``jax_disable_most_optimizations``
    off, every other with it on (this file's top).  The flag is no part of a
    jitted function's cache key, so a change of level drops what the worker
    has compiled: marked cases stand together."""
    want = request.node.get_closest_marker("optimised") is None
    import jax
    if jax.config.read("jax_disable_most_optimizations") != want:
        jax.clear_caches()
        jax.config.update("jax_disable_most_optimizations", want)


@pytest.fixture(scope="session")
def v5e():
    """One chip of a described (not attached) v5e; libtpu is loaded here,
    inside a test and once a worker, never while a module is imported."""
    import jax
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return jax.sharding.SingleDeviceSharding(topo.devices[0])


def pytest_terminal_summary(terminalreporter):
    """The ten longest files and the sum over all (under ``-n`` too: the
    controller holds every worker's reports), so that a session reads where
    the limit's seconds went from the run's own log (ROADMAP.md D0)."""
    from durations import longest_files
    total, files = longest_files(
        (report.nodeid, report.duration)
        for reports in terminalreporter.stats.values() for report in reports
        if hasattr(report, "duration") and hasattr(report, "nodeid"))
    if not files:
        return
    terminalreporter.section("seconds by file")
    for spent, tests, path in files:
        terminalreporter.write_line(f"{spent:8.1f} s {tests:5d} tests  {path}")
    terminalreporter.write_line(f"{total:8.1f} s over all files and workers")


def pytest_collection_modifyitems(items):
    """The longest files of the last measured run first, a file that run did
    not know before them all (``tests/pins/seconds.json``, written by
    ``tests/durations.py`` from a run's junit file): ``--dist loadfile``
    hands files out in the order they were collected, and a long file that
    starts last is a tail of its own behind five idle workers."""
    import json
    with open(os.path.join(os.path.dirname(__file__), "pins",
                           "seconds.json")) as f:
        seconds = json.load(f)
    items.sort(key=lambda item: -seconds.get(
        item.nodeid.split("::")[0], float("inf")))
