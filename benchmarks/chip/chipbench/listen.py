"""The program's spans, as it reports them through `jax.monitoring`.

Importing this module registers one duration listener. It keeps
(perf_counter at receipt, span name, seconds, args) for every event under
`/repro/`: the program's `repro.obs.trace` spans, named here without the
prefix. A span ends when its event arrives, or `late_us` microseconds
before where the program reported it after the fact, and began `seconds`
before its end.
Per-layer readers import this module, and the harness loads readers before
the window of a --trace 1 run only, so an untraced run has no listener and
pays nothing for one. A program that reports no such events leaves the
list empty, and the readers return None.

Spans the readers use (see the program's `gateway/workers.py`):
`worker.pump` (arg `stepped`), `worker.step`, `worker.idle`,
`engine.wait`, `engine.pending`, and `engine.step` (prefill: `tokens`,
`padded`, `reused`).
"""
from __future__ import annotations

import time
from typing import List, Optional, Tuple

import jax

PREFIX = "/repro/"
EVENTS: List[Tuple[float, str, float, dict]] = []


def _on(event: str, secs: float, **args):
    if event.startswith(PREFIX):
        EVENTS.append((time.perf_counter(), event[len(PREFIX):], secs, args))


jax.monitoring.register_event_duration_secs_listener(_on)


def spans(name: str, **match) -> List[Tuple[float, float, dict]]:
    """(start, end, args) of every span `name` whose args hold `match`."""
    out = []
    for t, n, secs, args in list(EVENTS):
        if n == name and all(args.get(k) == v for k, v in match.items()):
            end = t - args.get("late_us", 0) * 1e-6
            out.append((end - secs, end, args))
    return out


def covered(name: str, a: float, b: float, **match) -> float:
    """Seconds of [a, b] inside spans `name`, each span clipped to it."""
    return sum(max(0.0, min(e, b) - max(s, a))
               for s, e, _ in spans(name, **match))


def window_share(run, seconds: float) -> float:
    """`seconds` as a share of the traced window, in %."""
    a, b = run.trace_host
    return 100.0 * seconds / (b - a)


def engine_host_share(run) -> Optional[float]:
    """Share of the traced window the replica's worker spent in the
    engine's own host work: `worker.step` less the `engine.wait` inside
    it, in %; None where the program reported no step there."""
    if not run.trace_host:
        return None
    a, b = run.trace_host
    step = covered("worker.step", a, b)
    if step <= 0:
        return None
    return window_share(run, step - covered("engine.wait", a, b))
