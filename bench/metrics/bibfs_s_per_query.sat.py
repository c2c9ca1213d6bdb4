"""Device seconds of the BiBFS program (`bounded_bibfs`) per answered
query, from the trace."""
from benchlib import devicetrace


def read(run):
    if run.trace is None or not run.answered:
        return None
    s = run.trace.program_seconds(devicetrace.BIBFS_PROGRAM)
    return s / run.answered if s > 0 else None
