"""Query microbatches dispatched between the chunks of a pipelined
update, per committed tick: the mean over the window's ticks. Each is a
full-width BiBFS on the device while the update is in flight. A program
that does not mark them, or serves synchronously, gives nothing."""
from benchlib import serverecord


def read(run):
    rec = serverecord.of(run)
    chunks = getattr(rec, "update_chunks", None) if rec else None
    if not chunks or len(chunks) != run.ticks \
            or not any(sum(c.values()) for c in chunks):
        return None
    marks = [getattr(m, "between_chunks", None) for m in rec.microbatches]
    if None in marks:
        return None
    return sum(marks) / len(chunks)
