"""The harness is driven by data: a cell, a configuration and a per-layer
metric come as new files and appended entries; ``run.py`` knows none by
name; a run without a chip prints no result."""
import hashlib
import http.server
import json
import os
import re
import shutil
import subprocess
import sys
import threading
import time

import pytest

from benchmark.lib import stats, traffic

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
PROGRAM = ("main.py", "homebrewnlp_tpu", "scripts", "native", "configs")


def _bench(with_held_back: bool = False):
    """``BENCHMARK.json``; with the entries of the cells held back beside it
    (``benchmark/held_back/``) where a test checks files against entries."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    held_dir = os.path.join(REPO, "benchmark", "held_back")
    for name in sorted(os.listdir(held_dir)) if with_held_back else ():
        with open(os.path.join(held_dir, name)) as f:
            held = json.load(f)
        assert len(held["why_held_back"]) > 100
        for key in ("workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + held[key]
    return bench


def _run(root, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, ".jax_cache"))
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def _copy_benchmark(tmp_path, with_program: bool) -> str:
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    if with_program:
        for name in PROGRAM:
            os.symlink(os.path.join(REPO, name), os.path.join(root, name))
    return root


def _digests(root):
    out = {}
    for base, _dirs, files in os.walk(os.path.join(root, "benchmark")):
        if "/out" in base or "__pycache__" in base:
            continue
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = \
                    hashlib.sha256(f.read()).hexdigest()
    return out


def a_cell_a_configuration_and_a_metric_arrive_as_new_files_test(tmp_path):
    root = _copy_benchmark(tmp_path, with_program=True)
    before = _digests(root)
    bench_dir = os.path.join(root, "benchmark")
    with open(os.path.join(bench_dir, "configs", "32big_mixer.json")) as f:
        config = json.load(f)
    config["name"] = "throwaway"
    config["config"].update(depth=2, heads=2, features_per_head=32,
                            sequence_length=128, train_batch_size=4,
                            interleaved_datasets=4)
    with open(os.path.join(bench_dir, "configs", "throwaway.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "reference", "throwaway.py"), "w") as f:
        f.write("import importlib\nforward = importlib.import_module("
                "'benchmark.reference.32big_mixer').forward\n")
    with open(os.path.join(bench_dir, "workloads",
                           "train_32big_mixer_b32.json")) as f:
        cell = json.load(f)
    # a new cell's file carries its own weights_seed, an addition like any
    # other: the reader below refuses a file without it
    cell.update(name="train_throwaway", config="throwaway", weights_seed=3,
                weights_seed_why="test: a dense toy, nothing to sweep")
    with open(os.path.join(bench_dir, "workloads", "train_throwaway.json"),
              "w") as f:
        json.dump(cell, f)
    with open(os.path.join(bench_dir, "metrics", "throwaway_steps.py"),
              "w") as f:
        f.write('LAYER = "L1_host_loop"\n'
                'MOVES = "train_tokens_per_sec_chip"\n\n\n'
                'def read(run):\n'
                '    return run.result.counters["steps"]\n')
    bench = _bench()
    bench["configs"].append({"name": "throwaway", "source": "test",
                             "file": "benchmark/configs/throwaway.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "train_throwaway",
                               "config": "throwaway", "traffic": "test",
                               "chips": 1, "why": "test"})
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "train_32big_mixer_b32" in metric.get("workloads", ()):
            metric["workloads"].append("train_throwaway")
    bench["per_layer"].append({
        "name": "throwaway_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "L1_host_loop",
        "moves": "train_tokens_per_sec_chip",
        "workloads": ["train_throwaway"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    done = _run(root, "--workload", "train_throwaway", "--seed", "3",
                "--seconds", "1", "--trace", "1", "--rehearse-cpu")
    assert done.returncode == 10, done.stdout[-3000:] + done.stderr[-3000:]
    last = done.stdout.strip().splitlines()[-1]
    assert last.startswith("REHEARSAL (not a result): correct=True")
    assert "'throwaway_steps'" in last and "'init_s'" in last
    with open(os.path.join(bench_dir, "out", "rehearsal", "train_throwaway",
                           "result.json")) as f:
        result = json.load(f)
    assert result["line"]["metrics"]["throwaway_steps"]["value"] == \
        result["counters"]["steps"] > 0
    assert result["checks"]["logits_agree"] and \
        result["checks"]["no_compile_in_window"]
    assert result["counters"]["weights_seed"] == 3
    assert "seeds: weights_seed 3 (the cell's file)" in done.stdout
    after = _digests(root)
    assert {k: after[k] for k in before} == before, \
        "an existing file of the benchmark was edited"
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/throwaway.json",
        "benchmark/metrics/throwaway_steps.py",
        "benchmark/reference/throwaway.py",
        "benchmark/workloads/train_throwaway.json"]


# ---- two seeds make a run: the cell's file deals out the weights, --seed
# ---- the data ---------------------------------------------------------------

def _train_cells():
    return [w["name"] for w in _bench()["workloads"]
            if w["name"].startswith("train_")]


@pytest.mark.parametrize("cell", _train_cells())
def every_train_cell_names_its_weights_seed_test(cell):
    from benchmark.drivers.train import cell_weights_seed
    from benchmark.lib.cell import load_cell
    loaded = load_cell(cell)
    assert isinstance(cell_weights_seed(loaded), int)
    why = loaded.spec["weights_seed_why"]
    assert len(why) > 40
    held = loaded.model_config().get("experts_held", 0)
    # a cell that holds a share of its experts gives the sweep it was
    # chosen from; any other cell is 0 and says why nothing was swept
    assert ("lower median" in why) if held else loaded.spec["weights_seed"] == 0


@pytest.mark.parametrize("spec", [
    {}, {"weights_seed": 3}, {"weights_seed": 3, "weights_seed_why": " "},
    {"weights_seed": "3", "weights_seed_why": "a string is no seed"},
    {"weights_seed": True, "weights_seed_why": "nor is a flag"},
    {"weights_seed": None, "weights_seed_why": "nor is null"}])
def a_cell_file_without_its_weights_seed_fails_loudly_test(spec):
    import types
    from benchmark.drivers.train import cell_weights_seed
    cell = types.SimpleNamespace(name="train_nameless", spec=spec)
    with pytest.raises(KeyError) as err:
        cell_weights_seed(cell)
    assert "train_nameless" in str(err.value)
    assert "weights_seed" in str(err.value)
    assert cell_weights_seed(types.SimpleNamespace(
        name="train_named", spec={"weights_seed": 5,
                                  "weights_seed_why": "chosen"})) == 5


def the_seed_deals_out_the_data_and_the_cells_file_the_weights_test():
    """A CPU rehearsal of a held-expert cell at two ``--seed``s: the same
    weights (the checksums of the embedding and of the first sparse layer's
    first parameter, the router's matrix), other batches; ``--weights-seed``,
    the sweep's override, moves the weights and not the batch."""
    cell = "train_laguna_s_2_1_ep32_s8k"
    seen = []
    for extra in (["--seed", "1"], ["--seed", "2"],
                  ["--seed", "2", "--weights-seed", "12345"]):
        done = _run(REPO, "--workload", cell, *extra, "--seconds", "1",
                    "--trace", "0", "--rehearse-cpu")
        assert done.returncode == 10, done.stdout[-3000:] + done.stderr[-3000:]
        line = [ln for ln in done.stdout.splitlines()
                if ln.startswith("seeds: weights_seed ")]
        assert len(line) == 1 and "crc32" in line[0]
        with open(os.path.join(REPO, "benchmark", "out", "rehearsal", cell,
                               "result.json")) as f:
            counters = json.load(f)["counters"]
        assert len(counters["param_crc32"]) == 2
        assert any("/moe_" in name for name in counters["param_crc32"])
        seen.append(counters)
    one, two, swept = seen
    with open(os.path.join(REPO, "benchmark", "workloads",
                           cell + ".json")) as f:
        assert one["weights_seed"] == two["weights_seed"] == \
            json.load(f)["weights_seed"]
    assert one["param_crc32"] == two["param_crc32"]
    assert one["first_batch_crc32"] != two["first_batch_crc32"]
    assert swept["weights_seed"] == 12345
    assert swept["first_batch_crc32"] == two["first_batch_crc32"]
    assert set(swept["param_crc32"].values()).isdisjoint(
        two["param_crc32"].values())


def on_the_cpu_without_the_flag_nothing_is_printed_as_a_result_test():
    done = _run(REPO, "--workload", "train_32big_mixer_b32", "--seed", "1",
                "--seconds", "1", "--trace", "0", timeout=300)
    assert done.returncode == 3
    assert "needs 1 TPU chip(s)" in done.stderr
    assert '"metrics"' not in done.stdout and "REHEARSAL" not in done.stdout


def outside_a_checkout_of_the_program_nothing_runs_test(tmp_path):
    root = _copy_benchmark(tmp_path, with_program=False)
    done = _run(root, "--workload", "train_32big_mixer_b32", "--seed", "1",
                "--seconds", "1", "--trace", "0", timeout=120)
    assert done.returncode == 4
    assert done.stdout.strip() == ""


def run_py_names_no_cell_configuration_or_metric_test():
    with open(os.path.join(REPO, "benchmark", "run.py")) as f:
        text = f.read()
    bench = _bench(with_held_back=True)
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in bench[key]]
    assert len(names) > 25
    assert [n for n in names if n in text] == []


# ---- BENCHMARK.json, the cells' files and the readers agree ----------------

def every_entry_has_its_file_test():
    bench = _bench(with_held_back=True)
    bench_dir = os.path.join(REPO, "benchmark")
    for config in bench["configs"]:
        with open(os.path.join(REPO, config["file"])) as f:
            doc = json.load(f)
        assert doc["name"] == config["name"]
        assert sorted(doc["reduced"]) == sorted(config["reduced"])
        assert os.path.exists(os.path.join(
            bench_dir, "reference", config["name"] + ".py"))
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        with open(os.path.join(bench_dir, "workloads",
                               cell["name"] + ".json")) as f:
            spec = json.load(f)
        assert (spec["config"], spec["chips"]) == \
            (cell["config"], cell["chips"])
        assert os.path.exists(os.path.join(bench_dir, "drivers",
                                           spec["driver"] + ".py"))
        for key in ("end_to_end", "per_layer"):
            listed = [m["name"] for m in bench[key]
                      if cell["name"] in m.get("workloads", [cell["name"]])]
            assert sorted(spec[key]) == sorted(listed), (cell["name"], key)
        assert "setup_s" in spec["end_to_end"]
    for metric in bench["per_layer"]:
        import importlib
        mod = importlib.import_module("benchmark.metrics." + metric["name"])
        assert (mod.LAYER, mod.MOVES) == (metric["layer"], metric["moves"])
        assert metric["moves"] in end_to_end
        for cell in metric.get("workloads",
                               [c["name"] for c in bench["workloads"]]):
            moved = next(m for m in bench["end_to_end"]
                         if m["name"] == metric["moves"])
            assert cell in moved.get("workloads", [cell]), \
                (metric["name"], cell)


def the_contracts_limits_hold_test():
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert sum(c["chips"] == 4 for c in bench["workloads"]) <= \
        max(1, len(bench["workloads"]) // 4)
    for key in ("configs", "workloads"):
        assert all(len(e["why"]) <= 200 for e in bench[key])
    assert all(m["bound"] <= 0.1 for m in bench["end_to_end"])
    widths = ("features_per_head", "heads", "group_linear_factor",
              "intermediate_feed_forward_multiplier_multiplier")
    for config in bench["configs"]:
        assert not set(config["reduced"]) & set(widths)
    pairs = [(c["config"], c["traffic"]) for c in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10
    # names, and a per-layer metric's layer, are plain: the driver refuses
    # "L0 entry / L5 runtime" before any run (it did, PR 22)
    held = _bench(with_held_back=True)
    plain = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in held[key]]
    assert len(set(names)) == len(names)
    for name in names + [c["traffic"] for c in held["workloads"]]:
        assert plain.match(name), name
    for metric in held["per_layer"]:
        assert re.match(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z",
                        metric["layer"]), metric
    files = [os.path.join(d, f) for d, _, fs in os.walk(
        os.path.join(REPO, "benchmark")) for f in fs]
    assert [p for p in files
            if not re.match(r"[A-Za-z0-9_./-]*\Z", os.path.relpath(p, REPO))] == []


@pytest.mark.parametrize("name,repo_config", [
    ("32big_mixer", "configs/32big_mixer.json"),
    ("1b_long_context_d8", "configs/1b_long_context.json")])
def a_configuration_is_the_repos_config_plus_its_overrides_test(name,
                                                                 repo_config):
    with open(os.path.join(REPO, "benchmark", "configs", name + ".json")) as f:
        doc = json.load(f)
    with open(os.path.join(REPO, repo_config)) as f:
        source = json.load(f)
    assert doc["source"]["repo_config"] == repo_config
    expected = dict(source, **doc["overrides"])
    for key in doc["set_by_harness"]:
        expected.pop(key)
    assert doc["config"] == expected
    for key, change in doc["reduced"].items():
        assert source[key] == change["from"]
        assert doc["config"][key] == change["to"]
    assert set(doc["overrides"]) == set(doc["reduced"]) | set(doc["assumed"])


# ---- the traffic generator ---------------------------------------------------

MIX = {"rate_rps": 25.0, "arrivals": {"kind": "poisson"},
       "prompt_tokens": {"median": 96, "sigma": 0.6, "min": 8, "max": 384},
       "new_tokens": {"median": 48, "sigma": 0.5, "min": 8, "max": 128},
       "max_total": 512, "vocab": 256}


def _plain(schedule):
    return [(r.due_s, r.prompt, r.new_tokens) for r in schedule]


@pytest.mark.parametrize("extra", [
    {}, {"arrivals": {"kind": "bursts", "period_s": 2.0, "size": [8, 16]}},
    {"shared_prefix": {"pool": 4, "tokens": 200, "share": 0.7}}])
def a_schedule_is_a_function_of_its_seed_test(extra):
    params = dict(MIX, **extra)
    one = traffic.make_schedule(params, 7, 40.0)
    assert _plain(one) == _plain(traffic.make_schedule(params, 7, 40.0))
    assert _plain(one) != _plain(traffic.make_schedule(params, 8, 40.0))
    assert 0.8 * 1000 < len(one) < 1.2 * 1000
    assert [r.due_s for r in one] == sorted(r.due_s for r in one)
    assert all(0 <= r.due_s < 40.0 for r in one)
    assert all(r.new_tokens >= 1 and len(r.prompt) >= 8
               and len(r.prompt) + r.new_tokens <= 512
               and all(0 <= t < 256 for t in r.prompt) for r in one)
    if "shared_prefix" in extra:
        heads = [tuple(r.prompt[:200]) for r in one if len(r.prompt) > 200]
        assert len(set(heads)) <= 4 + 0.4 * len(heads)
        assert max(heads.count(h) for h in set(heads)) > 50
    if extra.get("arrivals", {}).get("kind") == "bursts":
        dues = [r.due_s for r in one]
        assert max(dues.count(d) for d in set(dues)) >= 8


class _Echo(http.server.BaseHTTPRequestHandler):
    stall_s = 0.0

    def do_POST(self):
        body = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
        time.sleep(self.stall_s)
        out = json.dumps({"tokens": body["tokens"]
                          + [0] * body["max_tokens"]}).encode()
        self.send_response(200)
        self.send_header("Content-Length", str(len(out)))
        self.end_headers()
        self.wfile.write(out)

    def log_message(self, *args):
        pass


@pytest.fixture
def echo_server():
    server = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Echo)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield server.server_address[1]
    server.shutdown()
    thread.join(timeout=10)
    assert not thread.is_alive()


def the_open_loop_times_from_the_due_time_and_reports_lateness_test(
        echo_server, monkeypatch):
    """One client and a server that takes 0.2 s a request: requests due
    0.05 s apart queue behind each other.  Their sends run late, and the
    latency counted from the DUE time shows the queue that a clock started
    at the send would hide."""
    monkeypatch.setattr(_Echo, "stall_s", 0.2)
    schedule = [traffic.Request(i, 0.05 * i, [1, 2, 3], 4) for i in range(4)]
    traffic.run_open_loop("127.0.0.1", echo_server, schedule, 10.0, clients=1)
    assert all(r.ok() and r.tokens == [1, 2, 3, 0, 0, 0, 0]
               for r in schedule)
    late = traffic.lateness_ms(schedule)
    assert len(late) == 4 and late[0] < 50
    assert late[3] > 400                       # sent ~0.6 s, due 0.15 s
    from_due = [r.done_s - r.due_s for r in schedule]
    from_send = [r.done_s - r.sent_s for r in schedule]
    assert from_due[3] > 0.6 and from_send[3] < 0.35
    assert all(r.sent_s >= r.due_s for r in schedule)


def enough_clients_send_on_time_test(echo_server, monkeypatch):
    monkeypatch.setattr(_Echo, "stall_s", 0.1)
    schedule = traffic.make_schedule(dict(MIX, rate_rps=40.0), 1, 1.0)
    traffic.run_open_loop("127.0.0.1", echo_server, schedule, 10.0,
                          clients=32)
    assert all(r.ok() for r in schedule)
    assert stats.percentile(traffic.lateness_ms(schedule), 50) < 20


def a_refused_connection_is_a_failed_request_test():
    req = traffic.Request(0, 0.0, [1], 1)
    traffic.post_completion("127.0.0.1", 1, req, 1.0)
    assert req.status == 599 and not req.ok()


# ---- statistics --------------------------------------------------------------

def percentiles_test():
    assert stats.percentile([4, 1, 3, 2], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.median([7]) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)


EXPOSITION = """# HELP hbnlp_serve_ttft_seconds admission to first token
# TYPE hbnlp_serve_ttft_seconds histogram
hbnlp_serve_ttft_seconds_bucket{le="0.25"} %d
hbnlp_serve_ttft_seconds_bucket{le="0.5"} %d
hbnlp_serve_ttft_seconds_bucket{le="1"} %d
hbnlp_serve_ttft_seconds_bucket{le="+Inf"} %d
hbnlp_serve_ttft_seconds_sum %s
hbnlp_serve_ttft_seconds_count %d
# TYPE hbnlp_serve_slots_occupied gauge
hbnlp_serve_slots_occupied 17
hbnlp_build_info{rev="abc"} 1
"""


def histogram_deltas_and_interpolated_quantiles_test():
    before = stats.parse_metrics(EXPOSITION % (2, 4, 4, 4, "1.5", 4))
    after = stats.parse_metrics(EXPOSITION % (2, 14, 24, 25, "16.5", 25))
    assert after["hbnlp_serve_slots_occupied"] == 17
    hist = stats.histogram_delta(before["hbnlp_serve_ttft_seconds"],
                                 after["hbnlp_serve_ttft_seconds"])
    assert hist == {"bounds": [0.25, 0.5, 1.0], "counts": [0, 10, 10, 1],
                    "sum": 15.0, "count": 21}
    # rank 10.5 of 21: half a sample into the (0.5, 1] bucket's ten
    assert stats.bucket_quantile(hist, 0.5) == pytest.approx(0.525)
    assert stats.bucket_quantile(hist, 0.25) == pytest.approx(0.38125)
    assert stats.bucket_quantile(hist, 1.0) == 1.0    # +Inf -> last bound
    empty = stats.histogram_delta(after["hbnlp_serve_ttft_seconds"],
                                  after["hbnlp_serve_ttft_seconds"])
    assert stats.bucket_quantile(empty, 0.5) is None
    whole = stats.histogram_delta(None, before["hbnlp_serve_ttft_seconds"])
    assert whole["counts"] == [2, 2, 0, 0]
