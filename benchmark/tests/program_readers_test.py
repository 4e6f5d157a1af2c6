"""The readers of what the program marks about itself (PR 23): ``tf_op`` off
the raw ``.xplane.pb``, the program's spans on a CPU rehearsal's trace, its
registry's set-up and compile series, and the scope join on the recorded v5e
fixture.  Where the program has no such span or counter — as the parent of
PR 23 has not — every reader returns ``None`` with a note and does not
raise."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import cell as cell_mod
from benchmark.lib import program_readers as P
from benchmark.lib.result import Result, Run
from benchmark.trace import event_metadata
from benchmark.trace import reduce as R

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
FIXTURE = os.path.join(REPO, "benchmark", "trace", "fixtures",
                       "v5e_fixture_step.xplane.pb")
CELL = "train_32big_mixer_b32"
NEW_METRICS = (
    "data_first_batch_s", "init_trace_s", "init_values_s", "init_place_s",
    "init_opt_state_s", "step_trace_lower_s", "step_backend_s",
    "step_dispatch_ms", "data_next_ms", "program_gap_share",
    "scope_attributed_share", "scope_mixing_time_share",
    "scope_group_linear_time_share", "scope_norm_time_share",
    "scope_optimizer_time_share")


def _run_of(trace_path, reduced=None, window="bench_window") -> Run:
    cell = cell_mod.load_cell(CELL)
    result = Result(end_to_end={}, correct=True, checks={}, attempted=0,
                    failed=0, device={"kind": "TPU v5 lite"}, spans={},
                    counters={}, trace_path=trace_path, trace_window=window)
    return Run(cell=cell, config=cell.model_config(True), result=result,
               trace=reduced)


# ---- the wire-format reader -------------------------------------------------

def tf_op_is_read_off_the_raw_xplane_test():
    planes = event_metadata.tf_ops(FIXTURE)
    assert list(planes) == ["/device:TPU:0"]
    ops = planes["/device:TPU:0"]
    # the matmul fusion carries its named scope ...
    assert ops["fusion.1"] == "jit(fixture_step)/matmuls/dot_general:"
    assert ops["flash_fwd_causal.1"] == \
        "jit(fixture_step)/flash_attention/flash_fwd_causal/pallas_call:"
    # ... and an instruction the compiler made (a DMA's done half, a bare
    # custom-call) has no such stat and is left out
    executed = {R.short_name(e.name) for e in R.load(FIXTURE).devices[0].ops}
    assert {"copy-done.1", "custom-call"} <= executed
    assert "copy-done.1" not in ops and "custom-call" not in ops
    assert set(ops) < executed | {"fusion", "fusion.2"}


def a_message_is_walked_field_by_field_test():
    # field 1 varint 300, field 2 bytes "ab", field 3 fixed32
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"ab" + \
        bytes([0x1D, 1, 0, 0, 0])
    assert list(event_metadata.fields(buf)) == [
        (1, 300), (2, b"ab"), (3, bytes([1, 0, 0, 0]))]
    with pytest.raises(ValueError):
        list(event_metadata.fields(bytes([0x0B])))      # a group: no XSpace


# ---- a CPU rehearsal: the program's spans, no device plane ------------------

@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    """One traced rehearsal of the flagship cell at its toy size, in a
    process of its own (it holds jax's profiler) and a checkout of its own
    (a copy of the benchmark beside links to the program, so that no other
    test's run shares its ``out/``)."""
    root = str(tmp_path_factory.mktemp("rehearsal") / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for name in ("main.py", "homebrewnlp_tpu", "scripts", "native",
                 "configs"):
        os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    done = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "2147483659", "--seconds", "1",
         "--trace", "1", "--rehearse-cpu"],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 10, done.stdout[-3000:] + done.stderr[-3000:]
    out = os.path.join(root, "benchmark", "out", "rehearsal", CELL)
    with open(os.path.join(out, "result.json")) as f:
        result = json.load(f)
    return {"stdout": done.stdout, "result": result,
            "trace": R.newest_xplane(os.path.join(out, "trace"))}


def a_rehearsal_prints_the_new_metrics_names_test(rehearsal):
    last = rehearsal["stdout"].strip().splitlines()[-1]
    assert last.startswith("REHEARSAL (not a result): correct=True")
    got = rehearsal["result"]["line"]["metrics"]
    # what the CPU can give: the registry's set-up sums and the host spans
    for name in NEW_METRICS[:9]:
        assert f"'{name}'" in last, (name, last)
        assert got[name]["value"] >= 0
    # what only a device plane gives reads nothing, and says so
    for name in NEW_METRICS[9:]:
        assert name not in got
        assert f"per-layer {name}: nothing to read" in rehearsal["stdout"]
    # the set-up spans split what the harness's clock saw around them
    spans = rehearsal["result"]["spans"]
    inside = sum(got[n]["value"] for n in (
        "data_first_batch_s", "init_trace_s", "init_values_s",
        "init_place_s", "init_opt_state_s"))
    assert 0 < inside <= spans["init_s"]
    assert 0 < got["step_trace_lower_s"]["value"] \
        + got["step_backend_s"]["value"] <= spans["compile_s"]


def host_span_medians_come_off_a_cpu_trace_test(rehearsal):
    run = _run_of(rehearsal["trace"])
    steps = cell_mod.load_cell(CELL).traffic(True)["trace_steps"]
    for name in P.PROGRAM_SPANS:
        value = P.span_median_ms(run, name)
        assert value is not None and 0 < value < 1000, name
    assert f"train/step_dispatch: {steps} spans" in " ".join(run.notes)
    assert P.span_median_ms(run, "no/such_span") is None
    assert "no host span 'no/such_span'" in run.notes[-1]
    # no device plane: nothing reduced, so the device readers read nothing
    assert P.program_gap_share(run) is None
    assert P.scope_seconds(run) is None
    assert P.scope_attributed_share(run) is None
    assert P.scope_share(run, "body/attention") is None
    # and the raw reader says the same of the file
    assert event_metadata.tf_ops(rehearsal["trace"]) is None


# ---- the program's registry -------------------------------------------------

@pytest.fixture
def registry():
    from homebrewnlp_tpu import telemetry
    prev = telemetry.set_registry(telemetry.Registry())
    yield telemetry.registry()
    telemetry.set_registry(prev)


def an_empty_registry_reads_none_with_a_note_test(registry):
    """The parent of PR 23: the same benchmark files over a program without
    the spans and counters."""
    run = _run_of(None)
    for name in NEW_METRICS[:7]:
        assert cell_mod.load_metric(name).read(run) is None, name
    assert any("holds no span 'setup/model_init'" in n for n in run.notes)
    assert any("holds no hbnlp_compile_seconds_total" in n
               for n in run.notes)
    # and without a trace the trace readers read nothing either
    for name in NEW_METRICS[7:]:
        assert cell_mod.load_metric(name).read(run) is None, name


def set_up_sums_come_off_the_programs_registry_test(registry):
    from homebrewnlp_tpu import telemetry
    for name, seconds in (("setup/data_first_batch", 0.5),
                          ("setup/model_init", 60.0),
                          ("setup/place_params", 3.0),
                          ("setup/opt_init", 2.0), ("setup/init_wait", 0.25)):
        telemetry.Phase(name).rec(0.0, seconds)
    registry.counter("hbnlp_init_values_seconds_total").inc(45.0)
    registry.counter("hbnlp_init_values_total").inc(342)
    seconds = registry.counter("hbnlp_compile_seconds_total", "",
                               ("phase", "fun"))
    for phase, fun, value in (("trace", "step_fn", 4.0),
                              ("lower", "step_fn", 1.5),
                              ("backend", "step_fn", 6.0),
                              ("cache_load", "", 5.0),
                              ("trace", "multiply", 0.125),   # inside step_fn's
                              ("backend", "<lambda>", 9.0)):  # the reference's
        seconds.labels(phase, fun).inc(value)
    run = _run_of(None)
    read = {n: cell_mod.load_metric(n).read(run) for n in NEW_METRICS[:7]}
    assert read == {"data_first_batch_s": 0.5, "init_trace_s": 15.0,
                    "init_values_s": 45.0, "init_place_s": 3.0,
                    "init_opt_state_s": 2.0, "step_trace_lower_s": 5.5,
                    "step_backend_s": 6.0}
    notes = " ".join(run.notes)
    assert "342 parameter values made" in notes
    assert "setup/init_wait 0.2500 s" in notes
    assert "cache_load of every program in the process 5.000" in notes


# ---- the scope join on the recorded v5e trace -------------------------------

@pytest.fixture(scope="module")
def fixture_run():
    reduced = R.reduce(R.load(FIXTURE), "bench_window",
                       ("data_next", "dispatch", "fence"))
    return _run_of(FIXTURE, reduced)


def the_fixture_has_no_program_span_and_says_so_test(fixture_run):
    # recorded before the program had spans: the harness's only
    assert P.program_gap_share(fixture_run) is None
    assert "none of the program's spans" in fixture_run.notes[-1]
    assert P.span_median_ms(fixture_run, "train/step_dispatch") is None


def scopes_are_joined_by_short_name_and_folded_by_the_program_test(
        fixture_run, monkeypatch):
    # fixture_step has no model scope: everything folds to ``unscoped``
    scopes = P.scope_seconds(fixture_run)
    assert list(scopes) == ["unscoped"]
    assert scopes["unscoped"] == pytest.approx(fixture_run.trace["busy_s"])
    assert P.scope_attributed_share(fixture_run) == pytest.approx(0.0)
    assert P.scope_share(fixture_run, "body/attention") is None
    assert "no instruction of scope 'body/attention'" in \
        fixture_run.notes[-1]
    largest = [n for n in fixture_run.notes
               if n.startswith("largest unscoped instructions")][-1]
    assert "jit(fixture_step)/matmuls/dot_general:" in largest
    # with a folding that knows the fixture's scopes, the join adds up: the
    # four matmul fusions' self time over busy time
    monkeypatch.setattr(P, "_scope_key", lambda op_name: (
        "body/matmuls" if "/matmuls/" in op_name else "unscoped"))
    P._op_scopes.cache_clear()          # folded once a trace: fold again
    ops = fixture_run.trace["ops"]
    want = sum(ops[n] for n in ("fusion", "fusion.1", "fusion.2",
                                "convolution_tanh_fusion"))
    share = P.scope_share(fixture_run, "body/matmuls")
    assert share == pytest.approx(100 * want / fixture_run.trace["busy_s"])
    assert P.scope_attributed_share(fixture_run) == pytest.approx(share)
    P._op_scopes.cache_clear()


def every_new_metric_is_listed_by_the_three_train_cells_test():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    cells = ["train_32big_mixer_b32", "train_32big_mixer_dp2tp2",
             "train_1b_long_context_s16k"]
    # appended in the order PERF.md section 3 lists them (later PRs appended
    # theirs after them, so the list's tail is no longer these)
    assert tuple(m["name"] for m in bench["per_layer"]
                 if m["name"] in NEW_METRICS) == NEW_METRICS
    for name in NEW_METRICS:
        mod = cell_mod.load_metric(name)
        assert (mod.LAYER, mod.MOVES) == (entries[name]["layer"],
                                          entries[name]["moves"])
        # the three cells of PR 23 first; later cells appended their names
        assert entries[name]["workloads"][:3] == cells
        assert mod.__doc__ and len(mod.__doc__) > 40
