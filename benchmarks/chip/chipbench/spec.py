"""BENCHMARK.json and the files it names, found by name.

Layout under the benchmark's directory:

    configs/<config>.json     the configuration as it is run (its `file`)
    traffic/<traffic>.json    parameters of one traffic mix
    cells/<workload>.json     the correctness limits of one cell
    metrics/<metric>.py       the reader of one per-layer metric
    archs/<model_type>.py     all that depends on the architecture, for the
                              configurations whose `model_type` it is
                              (`ARCH_PARTS`; see archs/qwen3.py)

A later change adds a cell, or an architecture, by adding such files and an
entry in BENCHMARK.json; no file here lists them.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
from dataclasses import dataclass
from typing import Dict, List


ARCH_PARTS = ("program_config", "program_params", "layer_tensors",
              "global_tensors", "embed", "layer", "head", "work")


class SpecError(Exception):
    """BENCHMARK.json or a file it names is missing or malformed."""


@dataclass
class Cell:
    name: str
    config: dict            # the configuration file's contents
    config_entry: dict      # its entry in BENCHMARK.json
    arch: object            # archs/<its model_type>.py
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
    config = _read_json(os.path.join(checkout, entry["file"]))
    if "model_type" not in config:
        raise SpecError(f"{entry['file']} states no model_type")
    return Cell(
        name=workload,
        config=config,
        config_entry=entry,
        arch=arch(bench_dir, config["model_type"]),
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


def arch(bench_dir: str, model_type: str):
    """The module archs/<model_type>.py, imported as `archs.<model_type>`
    with the benchmark's directory on sys.path (where run.py puts it), so
    that a module reaches its siblings the same way; there is no
    fallback."""
    path = os.path.join(bench_dir, "archs", model_type + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no module {path} for model_type {model_type!r}")
    if bench_dir not in sys.path:
        sys.path.insert(0, bench_dir)
    mod = importlib.import_module("archs." + model_type)
    missing = [p for p in ARCH_PARTS if not callable(getattr(mod, p, None))]
    if missing:
        raise SpecError(f"{path} defines no {missing}")
    return mod


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
