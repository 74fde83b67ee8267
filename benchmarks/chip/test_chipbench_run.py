"""Whole runs of the harness off the chip, on the tiny cells: a sound run
is correct; a run with the timed path broken underneath is not; a run that
finds no TPU, or no program beside it, fails instead of falling back."""
import json
import os
import shutil
import subprocess
import sys
import time

import jax.numpy as jnp
import pytest

import tinycell
from chipbench import BENCH_DIR, CHECKOUT, harness, spec, system, traffic
from chipbench.faults import FAULTS

SEED = 2 ** 31 + 11


def _run(tmp, name="tiny-qwen.closed", plant=None, seconds=2.0):
    root = tinycell.make(str(tmp))
    bench = tinycell.bench_dir(root)
    cell = spec.load_cell(root, bench, name)
    return harness.run(cell, bench, root, SEED, seconds, False,
                       t_start=time.perf_counter(), require_chip=False,
                       compile_cache=False, plant=plant)


def test_sound_run_is_correct(tmp_path):
    res = _run(tmp_path)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"itl_p95_ms", "output_tok_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["served_logit_gap_max"]["value"] <= 1e-3
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(tmp_path, fault):
    res = _run(tmp_path, plant=FAULTS[fault])
    assert res["correct"] is False
    gap = res["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]


def test_control_reads_above_the_limit(tmp_path, monkeypatch):
    """The int8 control, read at the positions the program served and
    judged by the cell's own limits, is not correct where the program
    is."""
    monkeypatch.setattr(traffic, "BLOCK", 4)
    root = tinycell.make(str(tmp_path))
    bench = tinycell.bench_dir(root)
    cell = spec.load_cell(root, bench, "tiny-mistral.open")
    res = harness.run(cell, bench, root, SEED, 3.0, False,
                      t_start=time.perf_counter(), require_chip=False,
                      compile_cache=False, control=True)
    limit = res["checks"]["served_logit_gap_max"]["limit"]
    assert res["checks"]["served_logit_gap_max"]["value"] <= limit
    assert res["correct"] is True
    assert res["readings"]["control_logit_gap_max"] > 3 * limit
    assert res["control_correct"] is False
    gap = res["control_checks"]["control_logit_gap_max"]
    assert gap == {"value": res["readings"]["control_logit_gap_max"],
                   "limit": limit}


def test_no_tpu_fails_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload",
         "qwen3-1.7b.backlog", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=CHECKOUT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_checkout_without_the_program_fails(tmp_path):
    root = str(tmp_path)
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), root)
    bench = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = spec.load_cell(root, bench, "qwen3-1.7b.backlog")
    with pytest.raises(system.ProgramMissing):
        harness.run(cell, bench, root, SEED, 1.0, False,
                    t_start=time.perf_counter(), require_chip=False,
                    compile_cache=False)


def test_open_loop_reports_ttft(tmp_path, monkeypatch):
    monkeypatch.setattr(traffic, "BLOCK", 4)
    res = _run(tmp_path, name="tiny-mistral.open", seconds=3.0)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"ttft_p90_ms", "setup_s"}
    # blocks of 4 arrivals, each spanning 2 s, from 0.5 s before the
    # window: for this seed 7 of them are due in the 3 s window
    assert res["attempted"] == 7
    assert jnp.isfinite(res["metrics"]["ttft_p90_ms"]["value"])
