"""Reduction of a profiler trace (`.xplane.pb`) to device metrics.

The device planes (`/device:TPU:<n>`) carry a line of XLA modules (one event
per program execution, named `jit_<function>(<fingerprint>)`) and a line of
XLA ops (one event per HLO instruction, named by the instruction's text, so
a Pallas kernel reads `... custom_call_target="tpu_custom_call" ...`). The
host plane carries the host threads' spans, the benchmark's own
`jax.profiler.TraceAnnotation`s among them. Both share one clock.

- busy: the union of op intervals inside the traced window, averaged over
  the devices that ran anything; idle share is 1 - busy / window;
- per-program device time: modules wholly inside the window;
- kernel time: the `tpu_custom_call` ops inside one program's modules, so
  the kernel is found by what it is and where it runs, not by the name XLA
  happened to give it;
- breakdown: the ops that took most self time, and the idle gaps grouped
  by the innermost host span that covers at least half of each gap.
"""
from __future__ import annotations

import bisect
import gzip
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "chipbench.trace_window"
CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
GAP_MIN_NS = 10_000.0           # idle gaps shorter than 10 us are not named
HOST_SPAN_MAX_NS = 1e9          # host spans longer than this name no gap


@dataclass
class Device:
    name: str
    modules: List[Tuple[str, float, float]] = field(default_factory=list)
    ops: List[Tuple[str, float, float]] = field(default_factory=list)


@dataclass
class Trace:
    devices: List[Device]
    host: List[Tuple[str, float, float]]     # (name, start_ns, end_ns)


def load(path: str) -> Trace:
    """Read an .xplane.pb (or a gzipped one) with JAX's own reader."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = Device(plane.name)
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev.modules = [(e.name.split("(")[0], e.start_ns,
                                    e.start_ns + e.duration_ns)
                                   for e in line.events]
                elif line.name == "XLA Ops":
                    dev.ops = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events]
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return Trace(devices, host)


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_label(name: str) -> str:
    label = name.split(" = ")[0].lstrip("%")
    return label + " [tpu_custom_call]" if CUSTOM_CALL in name else label


@dataclass
class Reduced:
    window_ns: Tuple[float, float]
    busy_s: float                       # averaged over active devices
    programs: Dict[str, Tuple[int, float]]  # name -> (count, device s)
    kernels: Dict[str, Tuple[int, float]]   # program -> (calls, device s)
    device_ops: List[Tuple[str, float]]
    idle_gaps: List[Tuple[str, float]]

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def step_ms(self, program: str) -> Optional[float]:
        n, s = self.programs.get(program, (0, 0.0))
        return s / n * 1e3 if n else None


def idle_percent(run) -> float:
    """Share of the traced window in which no operation ran on the device,
    in %."""
    return 100.0 * run.trace.idle_share


def _window(trace: Trace) -> Tuple[float, float]:
    spans = [(a, b) for name, a, b in trace.host if name == WINDOW_SPAN]
    if spans:
        return min(spans)
    ops = [(a, b) for d in trace.devices for _, a, b in d.ops]
    if not ops:
        raise ValueError("the trace holds no device operation")
    return min(a for a, _ in ops), max(b for _, b in ops)


def reduce(trace: Trace, top: int = 10) -> Reduced:
    t0, t1 = _window(trace)
    inside = lambda a, b: a >= t0 and b <= t1       # noqa: E731
    busy, programs, kernels = [], defaultdict(lambda: [0, 0.0]), \
        defaultdict(lambda: [0, 0.0])
    self_time: Dict[str, float] = defaultdict(float)
    gaps: List[Tuple[float, float]] = []
    for dev in trace.devices:
        ops = [(n, max(a, t0), min(b, t1)) for n, a, b in dev.ops
               if b > t0 and a < t1]
        if not ops:
            continue
        merged = _union([(a, b) for _, a, b in ops])
        busy.append(sum(b - a for a, b in merged))
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] - edges[i] >= GAP_MIN_NS]
        mods = [m for m in dev.modules if inside(m[1], m[2])]
        for name, a, b in mods:
            programs[name][0] += 1
            programs[name][1] += (b - a) / 1e9
        every = sorted(dev.modules, key=lambda m: m[1])
        starts = [a for _, a, _ in every]

        def module_of(t: float) -> Optional[str]:
            i = bisect.bisect_right(starts, t) - 1
            return every[i][0] if i >= 0 and t < every[i][2] else None

        # self time: an op's duration less the ops nested inside it
        selfs: List[List] = []
        stack: List[Tuple[int, float]] = []
        for name, a, b in sorted(ops, key=lambda o: (o[1], -o[2])):
            while stack and a >= stack[-1][1]:
                stack.pop()
            prog = module_of(a)
            if stack:
                selfs[stack[-1][0]][1] -= b - a
            selfs.append([f"{prog}:{_op_label(name)}", b - a])
            stack.append((len(selfs) - 1, b))
            if CUSTOM_CALL in name and prog is not None and inside(a, b):
                kernels[prog][0] += 1
                kernels[prog][1] += (b - a) / 1e9
        for label, ns in selfs:
            self_time[label] += max(ns, 0.0) / 1e9
    if not busy:
        raise ValueError("no device operation inside the traced window")
    device_ops = sorted(((k, v) for k, v in self_time.items() if v > 0),
                        key=lambda kv: -kv[1])[:top]
    return Reduced(window_ns=(t0, t1), busy_s=sum(busy) / len(busy) / 1e9,
                   programs={k: (v[0], v[1]) for k, v in programs.items()},
                   kernels={k: (v[0], v[1]) for k, v in kernels.items()},
                   device_ops=device_ops,
                   idle_gaps=_name_gaps(gaps, trace.host, top))


def _name_gaps(gaps, host, top) -> List[Tuple[str, float]]:
    spans = sorted((a, b, n) for n, a, b in host
                   if n != WINDOW_SPAN and b - a <= HOST_SPAN_MAX_NS)
    starts = [s[0] for s in spans]
    total: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for ga, gb in gaps:
        # the innermost span that covers at least half of the gap; else
        # the one that covers most of it
        cover = []
        lo = bisect.bisect_left(starts, ga - HOST_SPAN_MAX_NS)
        hi = bisect.bisect_left(starts, gb)
        for a, b, n in spans[lo:hi]:
            ov = min(b, gb) - max(a, ga)
            if ov > 0:
                cover.append((2 * ov >= gb - ga, -(b - a), ov, n))
        half = [x for x in cover if x[0]]
        if half:
            best = max(half, key=lambda x: x[1])[3]
        elif cover:
            best = max(cover, key=lambda x: (x[2], x[1]))[3]
        else:
            best = "no host span"
        total[best] += (gb - ga) / 1e9
        count[best] += 1
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:top]
    return [(f"{n} ({count[n]} gaps of 10 us or more)", s) for n, s in ranked]
