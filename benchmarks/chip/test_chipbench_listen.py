"""The readers of the program's spans (`chipbench.listen`), on whole runs of
the tiny cells off the chip: the worker's spans tile its time, every reader
finds a number, and the prefill padding share is the one the served prompts
give."""
import copy
import time
import types

import pytest

from chipbench import listen  # first: the listener must see the whole run
import tinycell
from chipbench import BENCH_DIR, harness, spec

SEED = 2 ** 31 + 11
READERS = ("engine_host_share.prompt", "engine_host_share.backlog",
           "pump_share.prompt", "engine_pending_p90_ms.prompt",
           "prefill_pad_share.prompt")


@pytest.fixture(scope="module",
                params=["tiny-qwen.closed", "tiny-mistral.open"])
def served(request, tmp_path_factory):
    """A sound untraced run of a tiny cell, and the harness's Run of it,
    its whole window standing in for the traced one."""
    runs = []

    class Run(harness.Run):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            runs.append(self)

    root = tinycell.make(str(tmp_path_factory.mktemp("cell")))
    bench = tinycell.bench_dir(root)
    cell = spec.load_cell(root, bench, request.param)
    del listen.EVENTS[:]
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "Run", Run)
        res = harness.run(cell, bench, root, SEED, 2.0, False,
                          t_start=time.perf_counter(), require_chip=False,
                          compile_cache=False)
    assert res["correct"] is True
    run, = runs
    run.trace_host = (run.t0, run.t1)
    return run, bench


def test_worker_spans_tile_the_window(served):
    """idle + pump overhead + engine host + device wait = the window."""
    run, _ = served
    a, b = run.trace_host
    pump = listen.covered("worker.pump", a, b, stepped=1)
    step = listen.covered("worker.step", a, b)
    wait = listen.covered("engine.wait", a, b)
    idle = listen.covered("worker.idle", a, b)
    assert 0 < wait < step < pump
    assert idle + pump <= b - a + 1e-3      # placed where they ran: no overlap
    parts = idle + (pump - step) + (step - wait) + wait
    assert parts == pytest.approx(b - a, rel=0.05)


def test_every_new_reader_returns_a_number(served):
    run, bench = served
    for name in READERS:
        v = spec.metric_reader(bench, name)(run)
        assert isinstance(v, float) and v >= 0, name


def test_prefill_pad_share_is_the_served_prompts(served):
    """Over every prefill of the run, the share the prompts' lengths and
    their buckets give (no prompt shares a prefix, so none reuses)."""
    from repro.serve.step import bucket_len
    run, bench = served
    run = copy.copy(run)
    run.t0 = min(r["submit_t"] for r in run.records)
    run.t1 = time.perf_counter()
    cache_len = run.cell.config["serving"]["cache_len"]
    lengths = [len(r["prompt"]) for r in run.records if r["token_ts"]]
    padded = sum(bucket_len(n, cache_len) for n in lengths)
    want = 100.0 * (padded - sum(lengths)) / padded
    assert want > 0
    assert spec.metric_reader(bench, "prefill_pad_share.prompt")(run) == want


def test_readers_read_nothing_without_program_spans(monkeypatch):
    """A program that reports no spans (one older than them) leaves every
    new reader with None, not an error."""
    monkeypatch.setattr(listen, "EVENTS", [])
    run = types.SimpleNamespace(trace_host=(0.0, 8.0), t0=0.0, t1=8.0)
    for name in READERS:
        assert spec.metric_reader(BENCH_DIR, name)(run) is None, name
