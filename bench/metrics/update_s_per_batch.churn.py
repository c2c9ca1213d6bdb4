"""Device seconds of the update programs (apply, search, repair and
commit fixpoints) per committed update batch, from the trace."""
from benchlib import devicetrace


def read(run):
    if run.trace is None or not run.ticks:
        return None
    s = run.trace.program_seconds(devicetrace.UPDATE_PROGRAM)
    return s / run.ticks if s > 0 else None
