"""The trace reduction, on a small trace recorded on a TPU v5e (a reduced
decode: 4 slots, 2 layers, d_model 256, one Pallas paged-decode call per
layer, 8-page tables) and on a hand-built one."""
import os

import pytest

from chipbench import harness, trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
SMALL = os.path.join(HERE, "testdata", "small_trace.xplane.pb.gz")


@pytest.fixture(scope="module")
def small():
    return tr.reduce(tr.load(SMALL))


def test_recorded_trace_busy_and_window(small):
    # the recorded window is the host span the benchmark annotated
    assert small.window_s == pytest.approx(0.016742009, rel=1e-9)
    assert 0 < small.busy_s < small.window_s
    assert small.idle_share == pytest.approx(1 - small.busy_s
                                             / small.window_s)


def test_recorded_trace_programs_and_kernel(small):
    n, s = small.programs["jit_decode"]
    assert n == 3 and s == pytest.approx(145464e-9)
    assert small.step_ms("jit_decode") == pytest.approx(145464e-9 / 3 * 1e3)
    assert small.step_ms("jit_missing") is None
    # two layers per decode, three decodes: six kernel calls, found by
    # their custom-call target, not by XLA's name for them
    calls, secs = small.kernels["jit_decode"]
    assert calls == 6 and secs == pytest.approx(6.7799e-05)
    top, secs0 = small.device_ops[0]
    assert top.startswith("jit_decode:") and "[tpu_custom_call]" in top
    assert secs0 == pytest.approx(6.7799e-05)


def test_recorded_trace_breakdown(small):
    assert 0 < len(small.device_ops) <= 10
    assert all(a[1] >= b[1] for a, b in zip(small.device_ops,
                                            small.device_ops[1:]))
    named = sum(s for _, s in small.idle_gaps)
    assert 0 < named <= small.window_s - small.busy_s + 1e-12
    assert small.idle_gaps[0][0].startswith("bench.gateway_step (")


def _hand_trace():
    op = lambda name, a, b: (name, float(a), float(b))      # noqa: E731
    cc = '%k = f32[2] custom-call(), custom_call_target="tpu_custom_call"'
    dev = tr.Device("/device:TPU:0",
                    modules=[("jit_decode", 100_000, 200_000),
                             ("jit_prefill", 300_000, 350_000),
                             ("jit_decode", 900_000, 1_200_000)],
                    ops=[op("%while.1 = loop", 100_000, 200_000),
                         op(cc, 110_000, 150_000),
                         op("%fusion.2 = x", 150_000, 190_000),
                         op("%fusion.3 = y", 300_000, 350_000),
                         op(cc, 900_000, 1_000_000)])
    host = [(tr.WINDOW_SPAN, 0.0, 1_000_000.0),
            ("engine.step", 200_000.0, 300_000.0),
            ("sleep", 360_000.0, 900_000.0),
            ("outer", 0.0, 1_000_000.0)]
    return tr.Trace([dev], host)


def test_hand_trace_exact():
    r = tr.reduce(_hand_trace())
    assert r.window_s == pytest.approx(1e-3)
    # busy: [100, 200) + [300, 350) + [900, 1000) us, clipped to the window
    assert r.busy_s == pytest.approx(250e-6)
    assert r.idle_share == pytest.approx(0.75)
    # the second decode runs past the window: it is not a whole module in
    # it, but its kernel call inside the window counts
    assert r.programs == {"jit_decode": (1, 100e-6),
                          "jit_prefill": (1, 50e-6)}
    assert r.kernels["jit_decode"] == (2, pytest.approx(140e-6))
    ops = dict(r.device_ops)
    assert ops["jit_decode:while.1"] == pytest.approx(20e-6)   # self time
    assert ops["jit_decode:k [tpu_custom_call]"] == pytest.approx(140e-6)
    gaps = dict(r.idle_gaps)
    # each gap goes to the innermost span covering half of it or more
    assert gaps == {"engine.step (1 gaps of 10 us or more)":
                    pytest.approx(100e-6),
                    "sleep (1 gaps of 10 us or more)": pytest.approx(550e-6),
                    "outer (1 gaps of 10 us or more)": pytest.approx(100e-6)}


def test_no_device_operation_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace([], [(tr.WINDOW_SPAN, 0.0, 1.0)]))


def test_profiler_covers_the_end_of_the_window():
    """A traced run's window keeps its length; only its last
    TRACE_SECONDS are profiled (all of it when it is shorter)."""
    prof = harness.Profiler(False)
    prof.arm(100.0, 151.0)
    assert (prof.t0, prof.t1) == (151.0 - harness.TRACE_SECONDS, 151.0)
    prof.arm(100.0, 103.0)
    assert (prof.t0, prof.t1) == (100.0, 103.0)
