"""BENCHMARK.json and the files it names, found by name.

Layout under the benchmark's directory:

    configs/<config>.json     the configuration as it is run (its `file`)
    traffic/<traffic>.json    parameters of one traffic mix
    cells/<workload>.json     the correctness limits of one cell
    metrics/<metric>.py       the reader of one per-layer metric

A later change adds a cell by adding such files and an entry in
BENCHMARK.json; no file here lists them.
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass
from typing import Dict, List


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass
class Cell:
    name: str
    config: dict            # the configuration file's contents
    config_entry: dict      # its entry in BENCHMARK.json
    traffic_name: str
    traffic: dict
    limits: dict            # cells/<name>.json
    chips: int
    end_to_end: List[dict]  # BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None
    except json.JSONDecodeError as err:
        raise SpecError(f"{path}: {err}") from None


def load_benchmark(checkout: str) -> dict:
    return _read_json(os.path.join(checkout, "BENCHMARK.json"))


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(checkout: str, bench_dir: str, workload: str,
              bench: dict | None = None) -> Cell:
    bench = bench if bench is not None else load_benchmark(checkout)
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench.get("configs", [])}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload}: no config {w['config']!r}")
    entry = configs[w["config"]]
    return Cell(
        name=workload,
        config=_read_json(os.path.join(checkout, entry["file"])),
        config_entry=entry,
        traffic_name=w["traffic"],
        traffic=_read_json(os.path.join(bench_dir, "traffic",
                                        w["traffic"] + ".json")),
        limits=_read_json(os.path.join(bench_dir, "cells",
                                       workload + ".json")),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench.get("end_to_end", [])
                    if _reports(m, workload)],
        per_layer=[m for m in bench.get("per_layer", [])
                   if _reports(m, workload)],
    )


def metric_reader(bench_dir: str, name: str):
    """The `read(ctx)` function of metrics/<name>.py."""
    path = os.path.join(bench_dir, "metrics", name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no reader {path} for per-layer metric {name}")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    if not callable(getattr(mod, "read", None)):
        raise SpecError(f"{path} defines no read(ctx)")
    return mod.read


def readers(bench_dir: str, cell: Cell) -> Dict[str, object]:
    return {m["name"]: metric_reader(bench_dir, m["name"])
            for m in cell.per_layer}
