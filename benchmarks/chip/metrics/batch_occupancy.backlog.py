"""Scheduler: mean share of decode slots filled, over the decode dispatches
of the window."""


def read(run):
    calls = [live for t, live in run.decode_calls if run.t0 <= t <= run.t1]
    if not calls:
        return None
    slots = run.cell.config["serving"]["batch_slots"]
    return 100.0 * sum(len(c) for c in calls) / (len(calls) * slots)
