"""Gateway: 90th percentile of the wait from a request's submission to its
dispatch to the engine (the program's dispatch stamp), over the requests
due in the window. Measured from submission, not from the due time, so
the generator's own lateness is not charged to the gateway."""
from chipbench.latency import percentile


def read(run):
    xs = [r["dispatch_t"] - r["submit_t"] for r in run.records
          if r["segment"] == "window" and r["dispatch_t"] is not None]
    v = percentile(xs, 90)
    return None if v is None else v * 1e3
