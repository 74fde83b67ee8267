"""One run of one cell: set-up, the measured window, the check, the line.

Set-up (counted in setup_s, from process start to the window): the
parameters from the seed on the device, the gateway with the configuration's
engine settings, one warm-up request per prefill shape the mix can send (so
every program the window runs is compiled or loaded from the persistent
cache before it opens), and the mix's lead-in load.

The window: requests go through `Gateway.submit`, the durable queue, the
replica's worker thread and the engine. An open loop submits each request
when it is due; a closed loop keeps a fixed number in flight. Every token's
arrival is stamped on the host's clock by a callback of the benchmark's.
With --trace 1 the window's last `TRACE_SECONDS` are profiled, where the
load is steady, and the engine's decode and prefill dispatches are recorded
(live keys per slot, prompt tokens) for the per-layer readers. The window
keeps its length, so a traced run checks as many requests as any other.

After the window: the workers stop, the memory peak is read, the program's
state is freed, and the reference checks a seeded sample of what the
window served (`check`).
"""
from __future__ import annotations

import gc
import math
import os
import shutil
import sys
import tempfile
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from . import check, endtoend, spec, system, trace as tr
from .traffic import Generator

TRACE_SECONDS = 8.0         # the profiled end of a --trace 1 run's window
GRACE_S = 60.0              # wait past the close for the window's answers
WARM_UP_TIMEOUT_S = 900.0
STALL_S = 0.1               # a hold of the load generator worth logging


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def devices_for(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def enable_compile_cache(checkout: str) -> str:
    """JAX's persistent cache at a fixed path inside the checkout (or where
    JAX_COMPILATION_CACHE_DIR points), holding every program however fast
    it compiled, so a second run of a cell compiles nothing. The benchmark
    sets it itself rather than through the program's own helper, so that
    what a run caches cannot move with the program under test."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        checkout, ".jax_cache", "chipbench")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Watchdog:
    """What holds the load generator. When the load loop has not come round
    for HOLD_S, logs every Python thread's stack (at most DUMPS times). When
    the watchdog itself wakes HOLD_S late, the whole interpreter was held:
    it logs the CPU seconds the process used meanwhile (near the hold's
    length: a thread computed holding the GIL; near 0: the process waited
    or was off the CPU) and the stacks as it finds them then."""

    DUMPS = 3
    HOLD_S = 0.5

    def __init__(self):
        self.beat_t = time.perf_counter()
        self._dumps = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True,
                                        name="chipbench-watchdog")
        self._thread.start()

    def _dump(self, why: str):
        if self._dumps >= self.DUMPS:
            return
        self._dumps += 1
        names = {t.ident: t.name for t in threading.enumerate()}
        for ident, frame in sys._current_frames().items():
            if ident != threading.get_ident():
                stack = "".join(traceback.format_stack(frame)[-8:])
                log(f"watchdog: {why}; thread {names.get(ident, ident)}:\n"
                    f"{stack}")

    def _watch(self):
        last, cpu, dumped_for = time.perf_counter(), time.process_time(), None
        while not self._stop.wait(0.05):
            now, cpu_now = time.perf_counter(), time.process_time()
            if now - last > self.HOLD_S:
                self._dump(f"woke {now - last!r} s after its last look, the "
                           f"interpreter held; the process used "
                           f"{cpu_now - cpu!r} CPU s meanwhile")
            last, cpu = now, cpu_now
            beat = self.beat_t
            if now - beat > self.HOLD_S and beat != dumped_for:
                dumped_for = beat
                self._dump(f"load loop held {now - beat!r} s")

    def stop(self):
        self._stop.set()
        self._thread.join()


class CompileCounter:
    """Counts backend compiles (and their seconds) while it listens."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0

    def _on(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class Recorder:
    """Records the engine's dispatches in a traced run by shadowing two of
    its attributes on the instance: for each decode call, the number of
    keys each live slot attends; for each prefill, (start, real tokens)."""

    def __init__(self, eng):
        self.decode: List[tuple] = []
        self.prefill: List[tuple] = []
        dec, pre = eng._decode_tok, eng._paged_prefill_slot

        def decode(*args):
            live = [int(eng.pos[s]) + 2 for s in range(eng.slots)
                    if eng.active[s] is not None]
            self.decode.append((time.perf_counter(), live))
            return dec(*args)

        def prefill(slot, req, adm):
            self.prefill.append((time.perf_counter(), adm.n_reused,
                                 len(req.prompt) - adm.n_reused))
            return pre(slot, req, adm)

        eng._decode_tok = decode
        eng._paged_prefill_slot = prefill


class Profiler:
    """Profiles the end of the window, [t1 - TRACE_SECONDS, t1), from the
    main thread, marking it with a host span the reduction looks for. The
    profiler starts half a second before that and stops only after the load
    has stopped: stopping it writes the trace and holds the host for
    seconds."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.t0 = self.t1 = math.inf
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
            if enabled else None
        self.host = None            # (start, stop) on perf_counter
        self._ann = None
        self._on = False

    def arm(self, t0: float, t1: float):
        """The window is [t0, t1); profile its end."""
        self.t0, self.t1 = max(t0, t1 - TRACE_SECONDS), t1

    def poll(self, now: float):
        if not self.enabled:
            return
        import jax
        if not self._on and self.host is None and now >= self.t0 - 0.5:
            jax.profiler.start_trace(self.dir)
            self._on = True
        elif self._ann is None and self.host is None and now >= self.t0:
            self._ann = jax.profiler.TraceAnnotation(tr.WINDOW_SPAN)
            self._ann.__enter__()
            self._start = time.perf_counter()
        elif self._ann is not None and now >= self.t1:
            self._close()

    def _close(self):
        self._ann.__exit__(None, None, None)
        self.host = (self._start, time.perf_counter())
        self._ann = None

    def stop(self):
        import jax
        if self._ann is not None:
            self._close()
        if self._on:
            self._on = False
            jax.profiler.stop_trace()

    def xplane(self) -> Optional[str]:
        for root, _, files in os.walk(self.dir or ""):
            for f in files:
                if f.endswith(".xplane.pb"):
                    return os.path.join(root, f)
        return None

    def cleanup(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclass
class Rec:
    due: float
    prompt: List[int]
    max_new: int
    segment: str
    submit_t: float = 0.0
    token_ts: List[float] = field(default_factory=list)
    handle: object = None


def _submit(gw, planned, due: float) -> Rec:
    rec = Rec(due, planned.prompt, planned.max_new_tokens, planned.segment)
    stamp = rec.token_ts.append
    rec.submit_t = time.perf_counter()
    rec.handle = gw.submit(planned.prompt,
                           max_new_tokens=planned.max_new_tokens,
                           on_token=lambda _tok: stamp(time.perf_counter()))
    return rec


def _finished(rec: Rec) -> bool:
    return rec.handle.finished


def warm_up(gw, cell: spec.Cell, gen: Generator, log_fn=log):
    """One request per prefill shape the mix can send, one decode token
    each: loads (or compiles) every program the window will run."""
    from .traffic import prefill_lengths
    serving = cell.config["serving"]
    buckets = system.prefill_buckets(serving, prefill_lengths(cell.traffic))
    hs = [gw.submit(gen.prompt(n), max_new_tokens=2) for n in buckets]
    deadline = time.perf_counter() + WARM_UP_TIMEOUT_S
    while not all(h.finished for h in hs):
        if time.perf_counter() > deadline:
            raise RuntimeError("warm-up requests did not finish")
        time.sleep(0.01)
    bad = [h.status for h in hs if h.status != "done"]
    if bad:
        raise RuntimeError(f"warm-up requests ended {bad}")
    log_fn(f"warm-up: prefill shapes {buckets}")


def open_loop(gw, gen, seconds: float, lead: float, prof: Profiler,
              dog: Watchdog):
    records, window = [], []
    t0 = time.perf_counter() + lead + 0.05
    t1 = t0 + seconds
    prof.arm(t0, t1)
    it = gen.open_loop(seconds)
    nxt = next(it)
    while True:
        now = time.perf_counter()
        prof.poll(now)
        dog.beat_t = now
        if now >= t1 and (now > t1 + GRACE_S or all(
                r.token_ts or _finished(r) for r in window)):
            break
        due = t0 + nxt.due_s
        if now >= due:
            rec = _submit(gw, nxt, due)
            records.append(rec)
            if rec.segment == "window":
                window.append(rec)
            nxt = next(it)
            continue
        time.sleep(min(due - now, 0.01))
    return records, t0, t1


def closed_loop(gw, gen, seconds: float, lead: float, in_flight: int,
                first: int, prof: Profiler, dog: Watchdog):
    records, active = [], []
    it = gen.closed_loop(first=first)
    t0 = time.perf_counter() + lead
    t1 = t0 + seconds
    prof.arm(t0, t1)
    while True:
        now = time.perf_counter()
        prof.poll(now)
        dog.beat_t = now
        if now >= t1:
            break
        active = [r for r in active if not _finished(r)]
        while len(active) < in_flight:
            rec = _submit(gw, next(it), now)
            active.append(rec)
            records.append(rec)
        time.sleep(0.005)
    return records, t0, t1


@dataclass
class Run:
    """What the window left, in plain data (no reference to the program)."""
    cell: spec.Cell
    seed: int
    records: List[dict]
    t0: float
    t1: float
    setup_s: float
    trace: Optional[tr.Reduced] = None
    trace_host: Optional[tuple] = None
    decode_calls: List[tuple] = field(default_factory=list)
    prefill_calls: List[tuple] = field(default_factory=list)
    programs: Dict[str, str] = field(default_factory=dict)
    peaks: object = None

    @property
    def shape(self):
        """The architecture's work counts for this configuration."""
        return self.cell.arch.work(self.cell.config)

    def traced(self, calls):
        """The calls recorded while the profiler ran."""
        if not self.trace_host:
            return []
        a, b = self.trace_host
        return [c for c in calls if a <= c[0] <= b]


def run(cell: spec.Cell, bench_dir: str, checkout: str, seed: int,
        seconds: float, trace: bool, *, t_start: float,
        require_chip: bool = True, plant: Optional[Callable] = None,
        control: bool = False, compile_cache: bool = True) -> dict:
    """One run; returns the result line's object (and logs the rest).
    Tests drive it off the chip (require_chip=False, compile_cache=False)
    and may `plant` a fault in the gateway after warm-up."""
    import jax
    unknown = [m["name"] for m in cell.end_to_end
               if m["name"] not in endtoend.METRICS]
    if unknown:
        raise spec.SpecError(f"no end-to-end metric named {unknown}")
    readers = spec.readers(bench_dir, cell) if trace else {}
    phases = {}
    t = time.perf_counter()
    phases["start to here (interpreter, imports)"] = t - t_start
    devs = devices_for(cell.chips, require_chip)
    cache = enable_compile_cache(checkout) if compile_cache else None
    phases["devices"] = time.perf_counter() - t
    t = time.perf_counter()
    system.import_program(checkout)
    c, traffic = cell.config, cell.traffic
    serving = c["serving"]
    mcfg = cell.arch.program_config(c)
    phases["program import"] = time.perf_counter() - t
    with CompileCounter() as clock:
        t = time.perf_counter()
        params = system.make_params(cell.arch, c, mcfg, seed)
        phases["weights"] = time.perf_counter() - t
        t = time.perf_counter()
        gw = system.build_gateway(params, mcfg, serving)
        eng = gw.replicas[0].engine
        phases["build"] = time.perf_counter() - t
        t = time.perf_counter()
        gen = Generator(traffic, seed, c["vocab_size"])
        warm_up(gw, cell, gen)
        phases["warm-up"] = time.perf_counter() - t
        t_load = time.perf_counter()
        programs = {"decode": "jit_" + eng._decode_tok.__name__,
                    "prefill": "jit_" + eng._prefill_tok.__name__}
        if plant is not None:
            plant(gw)
        rec = Recorder(eng) if trace else None
        n0, s0 = clock.count, clock.seconds
        lead = float(traffic.get("lead_in_s", 0.0))
        prof = Profiler(trace)
        dog = Watchdog()
        try:
            if traffic["loop"] == "open":
                records, t0, t1 = open_loop(gw, gen, seconds, lead, prof,
                                            dog)
            else:
                n = math.ceil(traffic["in_flight_per_slot"]
                              * serving["batch_slots"])
                records, t0, t1 = closed_loop(gw, gen, seconds, lead, n,
                                              serving["batch_slots"], prof,
                                              dog)
        finally:
            dog.stop()
            prof.stop()
        in_window = clock.count - n0, clock.seconds - s0
        gw.shutdown()
    setup_s = t0 - t_start
    phases["profiler start, lead-in"] = t0 - t_load
    log(f"set-up {setup_s!r} s: " + ", ".join(
        f"{k} {v!r} s" for k, v in phases.items())
        + f"; {n0} compiles took {s0!r} s (cache {cache})")
    log(f"compiles from the lead-in to the close: {in_window[0]} "
        f"({in_window[1]!r} s)")
    failures = sum(r.failures for r in gw.replicas)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    plain = [{"due": r.due, "prompt": r.prompt, "segment": r.segment,
              "submit_t": r.submit_t, "token_ts": list(r.token_ts),
              "max_new": r.max_new, "status": r.handle.status,
              "output": r.handle.output,
              "dispatch_t": r.handle.metrics.dispatch_t} for r in records]
    decode_calls = rec.decode if rec else []
    prefill_calls = rec.prefill if rec else []
    del records, gw, eng, params, rec
    gc.collect()
    log(f"device arrays left before the check: "
        f"{sum(a.nbytes for a in jax.live_arrays())} bytes")
    out = Run(cell, seed, plain, t0, t1, setup_s, programs=programs,
              decode_calls=decode_calls, prefill_calls=prefill_calls,
              trace_host=prof.host)
    if traffic["loop"] == "open":
        # due in the window; one that never answered has failed
        window = [r for r in plain if r["segment"] == "window"]
        failed = sum(r["status"] in ("failed", "rejected")
                     or not r["token_ts"] for r in window)
    else:
        # in flight at some point of the window
        window = [r for r in plain if r["submit_t"] < t1 and not (
            r["token_ts"] and r["token_ts"][-1] < t0)]
        failed = sum(r["status"] in ("failed", "rejected") for r in window)
    late = sorted(r["submit_t"] - r["due"] for r in plain
                  if r["segment"] == "window")
    if traffic["loop"] == "open" and len(window) >= 4:
        half = len(window) // 2
        wait = [r["token_ts"][0] - r["due"] if r["token_ts"] else math.inf
                for r in window]
        log(f"time to first token, median of the window's first and second "
            f"halves: {sorted(wait[:half])[half // 2]!r} s, "
            f"{sorted(wait[half:])[(len(wait) - half) // 2]!r} s")
    log(f"window: {len(window)} requests, "
        f"{sum(len(r['token_ts']) for r in plain)} tokens served from the "
        f"lead-in to the close; generator lateness median "
        f"{(late[len(late) // 2] if late else 0.0)!r} s, max "
        f"{(late[-1] if late else 0.0)!r} s")
    held = [(round(r["due"] - t0, 3), round(r["submit_t"] - r["due"], 3))
            for r in plain if r["segment"] == "window"
            and r["submit_t"] - r["due"] >= STALL_S]
    log(f"generator held {STALL_S} s or more: {len(held)} requests "
        f"(due in the window at s, late by s: {held[:8]})")
    # the check, after the program's state is gone
    done = [r for r in plain if r["status"] == "done"
            and len(r["output"]) == r["max_new"] and r["token_ts"]
            and r["token_ts"][-1] >= t0]
    limits = cell.limits
    picked = check.sample(done, seed, limits["check"]["max_requests"])
    t = time.perf_counter()
    values = check.readings(cell.arch, c, seed, picked, traffic,
                            control=control)
    log(f"readings: {values}")
    log(f"reference over {len(picked)} requests took "
        f"{time.perf_counter() - t!r} s")
    values["replica_failures"] = failures
    ok, checks = check.judge(values, limits["limits"])
    correct = ok and failed == 0
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": jax.device_count(), "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(window), "failed": failed}
    if trace:
        path = prof.xplane()
        if path is None:
            raise RuntimeError("the profiler wrote no trace")
        from .peaks import peaks_for
        out.peaks = peaks_for(devs[0].device_kind) if require_chip else None
        out.trace = tr.reduce(tr.load(path))
        prof.cleanup()
        dev["busy_s"] = out.trace.busy_s
        dev["window_s"] = out.trace.window_s
        values_of = {m["name"]: readers[m["name"]](out)
                     for m in cell.per_layer}
        result["metrics"] = {m["name"]: {"value": values_of[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.per_layer
                             if values_of[m["name"]] is not None}
    else:
        result["metrics"] = {m["name"]: {
            "value": endtoend.METRICS[m["name"]](out), "unit": m["unit"]}
            for m in cell.end_to_end}
    result["device"] = dev
    if trace:
        result["breakdown"] = {"device_ops": out.trace.device_ops,
                               "idle_gaps": out.trace.idle_gaps}
    if control:
        result["readings"] = values
        result["control_correct"], result["control_checks"] = check.judge(
            values, check.control_limits(limits["limits"]))
    result["checks"] = checks
    for name, v in checks.items():
        log(f"check {name}: {v['value']!r} (limit {v['limit']!r})")
    return result
