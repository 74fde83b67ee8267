"""chip_smoke.py's own checks, driven on the CPU at a reduced size: it
prints no result without a TPU, its phase passes on a healthy run, and an
engine step that raises fails the run although the gateway fails that
replica forward."""
import importlib.util
import json
import os

import jax
import pytest

from repro.configs import registry
from repro.launch import compile_cache
from repro.serve.engine import ServeEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def smoke():
    """A fresh chip_smoke module per test, so patched constants stay
    local to it."""
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def on_cpu(smoke, monkeypatch):
    """Let run() accept the CPU, serve the reduced config at a small cache
    length, and keep the process's compile-cache setting untouched."""
    small = registry.get(smoke.ARCH, reduced=True)
    monkeypatch.setattr(smoke, "require_tpu", jax.devices)
    monkeypatch.setattr(registry, "get", lambda arch, reduced=False: small)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "off")
    monkeypatch.setattr(smoke, "CACHE_LEN", 64)
    monkeypatch.setattr(smoke, "GREEDY", [(16, 4), (24, 6), (30, 3)])
    monkeypatch.setattr(smoke, "SHARED_PREFIX", 16)
    monkeypatch.setattr(smoke, "SAMPLED", (8, 5))
    return smoke


def test_no_tpu_prints_no_result(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_healthy_run_reports_ok(on_cpu, capsys):
    assert on_cpu.main([]) == 0
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": jax.device_count()}}
    assert "3/3 agree" in out


def test_raising_engine_step_fails_the_run(on_cpu, monkeypatch, capsys):
    def step(self):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(ServeEngine, "step", step)
    assert on_cpu.main([]) == 1
    out, err = capsys.readouterr()
    assert '"ok"' not in out
    assert "injected device failure" in err
