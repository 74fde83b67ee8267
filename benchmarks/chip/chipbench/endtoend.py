"""End-to-end metrics, taken on the host's clock by the benchmark itself:
each request's due time, submission and the arrival of each of its tokens
are read with `time.perf_counter` in the benchmark's own code."""
from __future__ import annotations

from .latency import gaps_in_window, percentile, time_to_first_token, \
    tokens_in_window


def ttft_p90_ms(run) -> float:
    """90th percentile, over every request due in the window, of the time
    from when it was due to its first token."""
    xs = [time_to_first_token(r["due"], r["token_ts"])
          for r in run.records if r["segment"] == "window"]
    return percentile(xs, 90) * 1e3


def itl_p95_ms(run) -> float:
    """95th percentile of every gap between consecutive tokens of a
    request inside the window, pooled across requests."""
    gaps = [g for r in run.records
            for g in gaps_in_window(r["token_ts"], run.t0, run.t1)]
    return percentile(gaps, 95) * 1e3


def output_tok_s(run) -> float:
    """Output tokens emitted in the window over the window's length."""
    n = tokens_in_window((r["token_ts"] for r in run.records), run.t0,
                         run.t1)
    return n / (run.t1 - run.t0)


def setup_s(run) -> float:
    """Process start to the start of the window."""
    return run.setup_s


METRICS = {f.__name__: f for f in (ttft_p90_ms, itl_p95_ms, output_tok_s,
                                   setup_s)}
