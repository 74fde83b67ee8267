"""Engine step: device time of the prefill program per dispatch, from the
trace (program modules wholly inside the traced window)."""


def read(run):
    return run.trace.step_ms(run.programs["prefill"])
