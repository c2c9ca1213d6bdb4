"""Device seconds per pipelined update dispatch: the update programs'
device time in the trace (apply, seeds, search and repair chunks,
finish) over the window's `serve.update_chunk` dispatches. About how
long a query microbatch queues behind one chunk."""
from benchlib import devicetrace, serverecord


def read(run):
    if run.trace is None:
        return None
    rec = serverecord.of(run)
    chunks = getattr(rec, "update_chunks", None) if rec else None
    if not chunks or len(chunks) != run.ticks:
        return None
    dispatches = sum(sum(c.values()) for c in chunks)
    s = run.trace.program_seconds(devicetrace.UPDATE_PROGRAM)
    return s / dispatches if dispatches and s > 0 else None
