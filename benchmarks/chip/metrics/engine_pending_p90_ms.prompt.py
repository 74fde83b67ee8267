"""Scheduler: 90th percentile of a request's wait in the engine, from the
engine taking it (enqueue) to its admission into a slot, where its prefill
starts (`engine.pending`), over the requests admitted in the window."""
from chipbench import listen
from chipbench.latency import percentile


def read(run):
    xs = [e - s for s, e, _ in listen.spans("engine.pending")
          if run.t0 <= e <= run.t1]
    v = percentile(xs, 90)
    return None if v is None else v * 1e3
