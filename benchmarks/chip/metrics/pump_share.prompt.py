"""Gateway: share of the traced window the replica's worker spent pumping
around the engine step (the gateway lock, the dispatch loop, lease
heartbeats, notifications): each `worker.pump` that stepped, less its
`worker.step`, in %."""
from chipbench import listen


def read(run):
    if not run.trace_host:
        return None
    a, b = run.trace_host
    pump = listen.covered("worker.pump", a, b, stepped=1)
    if pump <= 0:
        return None
    return listen.window_share(run, pump - listen.covered("worker.step", a, b))
