"""Engine step: device time of the decode program per dispatch, from the
trace (program modules wholly inside the traced window)."""


def read(run):
    return run.trace.step_ms(run.programs["decode"])
