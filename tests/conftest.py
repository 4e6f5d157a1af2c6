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

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


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


#: files of 100 s and more in the driver's run (ROADMAP.md D0's table), the
#: longest first: ``--dist loadfile`` hands files out in the order they were
#: collected, and a long file that starts last (``zaya_test.py``) is a tail
#: of its own behind five idle workers
LONGEST_FIRST = (
    "olmo_hybrid_test.py", "kimi_linear_test.py", "laguna_test.py",
    "sala_test.py", "zaya_test.py", "granite_test.py",
    "kda_rule_kernel_test.py", "flash_fused_bwd_test.py", "nemotron_test.py",
    "ouro_test.py", "pod_lowering_test.py", "distributed_test.py",
    "kernel_steps_test.py", "chip_smoke_test.py", "flash_edge_cells_test.py",
    "remat_policy_test.py", "olmoe_test.py", "flash_window_test.py",
    "pipeline_parallel_test.py")


def pytest_collection_modifyitems(items):
    rank = {name: at for at, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(
        os.path.basename(str(item.fspath)), len(rank)))
