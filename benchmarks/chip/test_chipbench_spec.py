"""BENCHMARK.json and its files: each cell's configuration, traffic mix,
limits and metric readers are found by name, so a cell added as new files
needs no edit to an existing one."""
import hashlib
import json
import os
import re

import pytest

import tinycell
from chipbench import BENCH_DIR, CHECKOUT, endtoend, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            if "__pycache__" not in d:
                p = os.path.join(d, f)
                with open(p, "rb") as fh:
                    out[os.path.relpath(p, root)] = hashlib.sha256(
                        fh.read()).hexdigest()
    return out


def test_new_cell_is_found_by_name_without_editing_a_file(tmp_path):
    root = tinycell.make(str(tmp_path))
    bench = tinycell.bench_dir(root)
    before = _digest(bench)
    # a new per-layer metric: one reader file and one BENCHMARK.json entry
    with open(os.path.join(bench, "metrics", "prompt_tokens.tiny.py"),
              "w") as f:
        f.write("def read(run):\n"
                "    return float(sum(len(r['prompt'])"
                " for r in run.records))\n")
    doc = spec.load_benchmark(root)
    doc["per_layer"].append({"name": "prompt_tokens.tiny", "unit": "tokens",
                             "better": "higher", "source": "program_counter",
                             "layer": "gateway", "moves": "ttft_p90_ms",
                             "workloads": ["tiny-mistral.open"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(doc, f)
    cell = spec.load_cell(root, bench, "tiny-mistral.open")
    assert cell.config["name"] == "tiny-mistral"
    assert cell.traffic["loop"] == "open"
    assert cell.limits["limits"]["served_logit_gap_max"]["max"] == 1e-3
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p90_ms",
                                                     "setup_s"]
    readers = spec.readers(bench, cell)
    assert list(readers) == ["prompt_tokens.tiny"]

    class Run:
        records = [{"prompt": [1, 2, 3]}, {"prompt": [4]}]
    assert readers["prompt_tokens.tiny"](Run()) == 4.0
    # the files the benchmark already had are untouched
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before


def test_missing_files_are_reported(tmp_path):
    root = tinycell.make(str(tmp_path))
    bench = tinycell.bench_dir(root)
    os.remove(os.path.join(bench, "traffic", "open.json"))
    with pytest.raises(spec.SpecError, match="open.json"):
        spec.load_cell(root, bench, "tiny-mistral.open")
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell(root, bench, "no-such.cell")
    with pytest.raises(spec.SpecError, match="no reader"):
        spec.metric_reader(bench, "no_such_metric")


def test_committed_benchmark_is_complete():
    doc = spec.load_benchmark(CHECKOUT)
    assert doc["command"] == ["python3", "benchmarks/chip/run.py"]
    assert doc["paths"] == ["benchmarks/chip"]
    e2e = {m["name"]: m for m in doc["end_to_end"]}
    assert set(e2e) <= set(endtoend.METRICS) and "setup_s" in e2e
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for w in doc["workloads"]:
        cell = spec.load_cell(CHECKOUT, BENCH_DIR, w["name"], doc)
        reported = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer, w["name"]
        for m in cell.per_layer:
            assert m["moves"] in reported, (w["name"], m["name"])
        spec.readers(BENCH_DIR, cell)
        assert cell.traffic.get("greedy") is True
    for c in doc["configs"]:
        with open(os.path.join(CHECKOUT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert all(k in cfg for k in c["reduced"])
