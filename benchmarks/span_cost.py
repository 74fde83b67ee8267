"""Per-span cost of `repro.obs.trace`, on the host that runs this.

    PYTHONPATH=src python -m benchmarks.span_cost [--spans N]

Times `with otrace.span(...)` (no args, and three args as an engine step
passes them) in four states: nothing observing (the shared no-op), one
`jax.monitoring` duration listener, the ring armed (`enable()`), and a
`jax.profiler` capture collecting host spans. Prints one line per state,
microseconds per span, best of five repeats. A host-side number only: it
says nothing about device time.
"""
from __future__ import annotations

import argparse
import tempfile
import timeit

import jax

from repro.obs import trace as otrace


def _bare():
    with otrace.span("worker.pump"):
        pass


def _args():
    with otrace.span("engine.step", step="decode", live=32, keys=12000):
        pass


def _us(fn, n: int) -> float:
    return min(timeit.repeat(fn, number=n, repeat=5)) / n * 1e6


def measure(n: int) -> dict:
    out = {}

    def row(state):
        out[state] = {"no_args_us": _us(_bare, n), "three_args_us": _us(_args, n)}

    row("unobserved")
    listener = lambda event, secs, **args: None     # noqa: E731
    jax.monitoring.register_event_duration_secs_listener(listener)
    row("listener")
    jax.monitoring.unregister_event_duration_listener(listener)
    otrace.enable(capacity=1 << 10)
    row("ring")
    otrace.disable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            row("profile")
        finally:
            jax.profiler.stop_trace()
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spans", type=int, default=200_000)
    args = ap.parse_args(argv)
    for state, r in measure(args.spans).items():
        print(f"{state:10s} no args {r['no_args_us']:.3f} us/span, "
              f"three args {r['three_args_us']:.3f} us/span")


if __name__ == "__main__":
    main()
