"""Resolve a cell by name: ``BENCHMARK.json`` -> the cell's file, its
configuration's file, its driver, and the metrics it reports.

Nothing here knows a cell, a configuration or a metric by name.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import typing

#: the repository root: this file is <root>/benchmark/lib/cell.py
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
#: what a checkout of the program holds beside the benchmark's own files
NEEDS = ("main.py", "homebrewnlp_tpu", "scripts/text2records.py")


class NotACheckout(Exception):
    pass


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: dict                  # the cell's entry in BENCHMARK.json
    spec: dict                   # benchmark/workloads/<name>.json
    config_name: str
    config_doc: dict             # benchmark/configs/<config>.json
    end_to_end: typing.List[dict]
    per_layer: typing.List[dict]
    run_seconds: int

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])

    def model_config(self, rehearsal: bool = False) -> dict:
        """The configuration as this cell runs it: the configuration's file,
        then the cell's own overrides (batch, layout), then — in a CPU
        rehearsal only — the cell's toy shape."""
        cfg = dict(self.config_doc["config"])
        cfg.update(self.spec.get("overrides", {}))
        if rehearsal:
            cfg.update(self.spec.get("rehearsal", {}).get("config", {}))
        return cfg

    def traffic(self, rehearsal: bool = False) -> dict:
        out = dict(self.spec.get("traffic", {}))
        if rehearsal:
            out.update(self.spec.get("rehearsal", {}).get("traffic", {}))
        return out


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str) -> Cell:
    missing = [n for n in NEEDS if not os.path.exists(os.path.join(ROOT, n))]
    if missing:
        raise NotACheckout(f"{ROOT} is not a checkout of the program: "
                           f"missing {missing}")
    bench = _load(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    held = os.path.join(BENCH, "held_back", f"{name}.json")
    if name not in entries and os.path.exists(held):
        # a cell whose entries wait outside BENCHMARK.json (the file says
        # why): it runs by name like any other, the driver does not list it
        for key, value in _load(held).items():
            if key in ("workloads", "end_to_end", "per_layer"):
                bench[key] = bench[key] + value
        entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(entries)})")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config_doc = _load(os.path.join(ROOT, configs[entry["config"]]["file"]))
    spec = _load(os.path.join(BENCH, "workloads", f"{name}.json"))
    return Cell(
        name=name, entry=entry, spec=spec, config_name=entry["config"],
        config_doc=config_doc,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
        run_seconds=int(bench["run_seconds"]))


def load_driver(kind: str):
    """``benchmark/drivers/<kind>.py``: one traffic kind, one module."""
    return importlib.import_module(f"benchmark.drivers.{kind}")


def load_reference(config_name: str):
    """``benchmark/reference/<config>.py``: the configuration's plain
    reference."""
    return importlib.import_module(f"benchmark.reference.{config_name}")


def load_metric(name: str):
    """``benchmark/metrics/<name>.py``: one per-layer metric's reader."""
    return importlib.import_module(f"benchmark.metrics.{name}")


def out_dir(cell: str, rehearsal: bool) -> str:
    parts = [BENCH, "out"] + (["rehearsal"] if rehearsal else []) + [cell]
    path = os.path.join(*parts)
    os.makedirs(path, exist_ok=True)
    return path


def data_dir(rehearsal: bool) -> str:
    parts = [BENCH, "out"] + (["rehearsal"] if rehearsal else []) + ["_data"]
    return os.path.join(*parts)
