"""Span tracer: one span call, on the device trace's clock.

Where does a token's latency actually go — queue wait, admission, jit
dispatch, waiting on the device, retire? Each ``span(name, **args)`` at an
instrumentation site does three things:

  * **Profiler.** While a ``jax.profiler`` capture is collecting (an
    operator's XProf capture, or a benchmark's traced run) it enters a
    ``jax.profiler.TraceAnnotation(name, **args)``, so the capture holds
    the program's spans on the same host plane and clock as the device's
    operations: an idle gap on the device lines up with what the host was
    doing.
  * **Monitoring.** On exit it reports its duration through
    ``jax.monitoring.record_event_duration_secs("/repro/" + name, secs,
    **args)``. Any registered duration listener sees every span; with none
    registered that is one empty loop.
  * **Ring.** Only while an operator has armed it (``enable()``, the serve
    launcher's ``--trace OUT.json``), finished spans land in a ring buffer
    (``capacity`` spans, oldest dropped first, drops counted) exported as
    Chrome trace events, the JSON the Perfetto UI at
    https://ui.perfetto.dev loads directly.

``add_span`` reports a span whose ends are known only afterwards (a
request's wait in the engine's queue; a worker's idle time, known to have
ended once the next pump has stepped) to monitoring, with arg ``late_us``
saying how long after its end, and to the ring; the profiler has no
retroactive form, so it holds no such span.

Arguments are ``str | int`` (``jax.monitoring``'s contract). Counts ride
as arguments: there is no second counter system. A span times what the
host does and never waits on the device itself: ``jit.*`` spans cover the
dispatch only, and ``engine.wait`` covers the point where the engine
already blocks on a result. With no profile collecting, no listener and no
ring, ``span()`` returns a shared no-op (``python -m benchmarks.span_cost``
measures what each state costs).

Track layout in the export: pid 1 ("serving host") holds the host/engine
spans, one tid per engine replica (the gateway itself shares tid 0 with
replica 0, which it drives synchronously). pid 2 ("requests") holds one
tid per request: a ``req<gid>`` span covering submit -> retire with
``queued`` / ``running`` phase spans nested inside — the Fig 6/7 queue
story, but per request and zoomable. pid 3 ("engine queues") holds one
tid per engine request: its ``engine.pending`` wait, from the engine
taking it to its admission into a slot.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

from jax._src import monitoring as _monitoring
from jax.monitoring import record_event_duration_secs
from jax.profiler import TraceAnnotation

HOST_PID = 1        # gateway/engine/jit spans, tid = replica id
REQUEST_PID = 2     # request-lifetime spans, tid = request gid
ENGINE_QUEUE_PID = 3    # engine.pending spans, tid = engine request id
EVENT_PREFIX = "/repro/"    # jax.monitoring event = EVENT_PREFIX + span name


class _Span:
    """One finished span. perf_counter seconds, duration >= 0."""
    __slots__ = ("name", "cat", "t0", "dur", "pid", "tid", "args")

    def __init__(self, name, cat, t0, dur, pid, tid, args):
        self.name = name
        self.cat = cat
        self.t0 = t0
        self.dur = dur
        self.pid = pid
        self.tid = tid
        self.args = args


def _report(tracer: Optional["Tracer"], name: str, t0: float, t1: float,
            cat: str, pid: int, tid: int, args: Dict[str, Any],
            late_us: Optional[int] = None):
    """A finished span to every listener of jax.monitoring, and to the
    ring of `tracer` when one is armed. A span reported after its end
    tells listeners by how much (`late_us`), so they can place it."""
    if late_us is None:
        record_event_duration_secs(EVENT_PREFIX + name, t1 - t0, **args)
    else:
        record_event_duration_secs(EVENT_PREFIX + name, t1 - t0, **args,
                                   late_us=late_us)
    if tracer is not None:
        tracer._record(_Span(name, cat, t0, t1 - t0, pid, tid,
                             dict(args) if args else None))


class _ActiveSpan:
    """Context manager for one span: a profiler annotation while a profile
    is collecting, reported on exit (see the module docstring)."""
    __slots__ = ("_tracer", "_name", "_cat", "_tid", "_args", "_ann", "_t0")

    def __init__(self, tracer: Optional["Tracer"], name: str, cat: str,
                 tid: int, args: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._tid = tid
        self._args = args

    def __enter__(self) -> "_ActiveSpan":
        if TraceAnnotation.is_enabled():
            self._ann = TraceAnnotation(self._name, **self._args)
            self._ann.__enter__()
        else:
            self._ann = None
        self._t0 = time.perf_counter()
        return self

    def set(self, **args):
        """Args known only at the span's end (the pages an admission
        evicted); reported with the rest on exit."""
        self._args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _report(self._tracer, self._name, self._t0, t1, self._cat, HOST_PID,
                self._tid, self._args)
        return False


class _NullSpan:
    """Shared no-op span: nothing would see this one."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def set(self, **args):
        pass

    def __exit__(self, *exc) -> bool:
        return False


_NULL = _NullSpan()


def _unobserved() -> bool:
    """No ring armed, no duration listener registered (jax.monitoring has
    no public getter for its listener list) and no profile collecting."""
    return (_TRACER is None
            and not _monitoring._event_duration_secs_listeners
            and not TraceAnnotation.is_enabled())


class Tracer:
    def __init__(self, capacity: int = 1 << 16):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=self.capacity)
        self.recorded = 0           # spans ever recorded
        self.dropped = 0            # spans evicted by the ring
        self._track_names: Dict[Tuple[int, int], str] = {}
        self._epoch = time.perf_counter()
        # leaf lock: worker threads record spans concurrently in async-
        # gateway mode, and exporting iterates the ring — a concurrent
        # append during that iteration raises RuntimeError, corrupting the
        # Perfetto export. Nothing under this lock calls out of the tracer.
        self._mu = threading.Lock()

    @property
    def epoch(self) -> float:
        """perf_counter origin of the exported timeline: every event's
        ``ts`` is ``(t - epoch) * 1e6``. Public so companion exporters
        (the flight recorder's lifecycle instants) can place their events
        on the same clock as the span events."""
        return self._epoch

    # -------------------------------------------------------- recording
    def span(self, name: str, *, cat: str = "serve", tid: int = 0,
             **args) -> _ActiveSpan:
        """A span recorded into this tracer's ring (and reported like any
        other)."""
        return _ActiveSpan(self, name, cat, tid, args)

    def add_span(self, name: str, t0: float, t1: float, *,
                 cat: str = "serve", pid: int = HOST_PID, tid: int = 0,
                 args: Optional[Dict[str, Any]] = None):
        """Record a span into the ring alone, retroactively from
        perf_counter endpoints — the request-lifetime spans are emitted
        this way at retire time, from the timestamps `GatewayMetrics`
        already keeps."""
        self._record(_Span(name, cat, t0, max(t1 - t0, 0.0), pid, tid,
                           args))

    def _record(self, span: _Span):
        with self._mu:
            self.recorded += 1
            if len(self._ring) == self.capacity:
                self.dropped += 1
            self._ring.append(span)

    def set_track_name(self, pid: int, tid: int, name: str):
        with self._mu:
            self._track_names[(pid, tid)] = name

    # -------------------------------------------------------- reduction
    def __len__(self) -> int:
        with self._mu:
            return len(self._ring)

    def stats(self) -> dict:
        """Flat counters for the unified metrics snapshot."""
        with self._mu:
            return {
                "enabled": True,
                "capacity": self.capacity,
                "spans_recorded": self.recorded,
                "spans_buffered": len(self._ring),
                "spans_dropped": self.dropped,
            }

    def events(self) -> list:
        """Chrome-trace-event dicts: ``ph="X"`` complete events (ts/dur
        in microseconds since the tracer's epoch) preceded by ``ph="M"``
        process/track name metadata, sorted by begin time."""
        with self._mu:
            track_names = dict(self._track_names)
            ring = list(self._ring)
        evs = []
        for pid, pname in ((HOST_PID, "serving host"),
                           (REQUEST_PID, "requests"),
                           (ENGINE_QUEUE_PID, "engine queues")):
            evs.append({"ph": "M", "name": "process_name", "pid": pid,
                        "tid": 0, "ts": 0,
                        "args": {"name": pname}})
        for (pid, tid), name in sorted(track_names.items()):
            evs.append({"ph": "M", "name": "thread_name", "pid": pid,
                        "tid": tid, "ts": 0, "args": {"name": name}})
        spans = sorted(ring, key=lambda s: (s.t0, -s.dur))
        for s in spans:
            ev = {"ph": "X", "name": s.name, "cat": s.cat,
                  "ts": (s.t0 - self._epoch) * 1e6, "dur": s.dur * 1e6,
                  "pid": s.pid, "tid": s.tid}
            if s.args:
                ev["args"] = dict(s.args)
            evs.append(ev)
        return evs

    def export(self, path) -> Path:
        """Write the Perfetto-loadable Chrome trace JSON."""
        path = Path(path)
        with open(path, "w") as f:
            json.dump({"traceEvents": self.events(),
                       "displayTimeUnit": "ms"}, f)
            f.write("\n")
        return path


# ------------------------------------------------------- process-global API
#
# One tracer per process keeps every instrumentation site a plain module
# call — no tracer threading through constructors that predate this
# subsystem — and matches the export format (one trace file per process).

_TRACER: Optional[Tracer] = None

# track names announced while no tracer was active. ReplicaWorker threads
# name their track once, at thread start — if tracing is enabled *after*
# start_workers (the common serve order: build fleet, then arm
# observability), a fresh Tracer would otherwise have no thread_name
# metadata for the per-replica tracks and every async-mode span would
# render on anonymous tracks. Bounded: only long-lived tracks (one per
# replica worker) announce through the module API.
_PENDING_TRACKS: Dict[Tuple[int, int], str] = {}
_PENDING_MU = threading.Lock()


def enable(capacity: int = 1 << 16) -> Tracer:
    """Install (and return) a fresh process-global tracer, pre-seeded
    with every track name announced before this call."""
    global _TRACER
    t = Tracer(capacity)
    with _PENDING_MU:
        t._track_names.update(_PENDING_TRACKS)
    _TRACER = t
    return _TRACER


def disable() -> Optional[Tracer]:
    """Stop tracing; returns the detached tracer so a caller can still
    export what was captured."""
    global _TRACER
    t, _TRACER = _TRACER, None
    return t


def active() -> Optional[Tracer]:
    return _TRACER


def enabled() -> bool:
    return _TRACER is not None


def span(name: str, *, cat: str = "serve", tid: int = 0, **args):
    """Instrumentation-site entry point: a context manager timing the
    enclosed code as span `name` with str/int `args`; the shared no-op
    when nothing would see it."""
    if _unobserved():
        return _NULL
    return _ActiveSpan(_TRACER, name, cat, tid, args)


def add_span(name: str, t0: float, t1: float, *, cat: str = "serve",
             pid: int = HOST_PID, tid: int = 0, **args):
    """Report a span from perf_counter endpoints known only afterwards:
    to monitoring, with arg `late_us` (microseconds from its end to now),
    and to the ring while one is armed."""
    if _TRACER is None and not _monitoring._event_duration_secs_listeners:
        return
    t1 = max(t1, t0)
    _report(_TRACER, name, t0, t1, cat, pid, tid, args,
            late_us=int((time.perf_counter() - t1) * 1e6))


def set_track_name(pid: int, tid: int, name: str):
    """Name a track on the active tracer AND remember it for tracers
    enabled later — a worker thread names its replica track exactly once,
    at thread start, which may precede `enable()`."""
    with _PENDING_MU:
        _PENDING_TRACKS[(pid, tid)] = name
    t = _TRACER
    if t is not None:
        t.set_track_name(pid, tid, name)
