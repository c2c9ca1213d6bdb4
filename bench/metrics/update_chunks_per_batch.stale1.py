"""Pipelined update dispatches per committed batch: the mean over the
window's ticks of the serve loop's `serve.update_chunk` dispatches,
counted by phase tag (seeds, search and repair chunks, the finish). A
program that does not count them, or serves synchronously, gives
nothing."""
from benchlib import serverecord


def read(run):
    rec = serverecord.of(run)
    chunks = getattr(rec, "update_chunks", None) if rec else None
    if not chunks or len(chunks) != run.ticks:
        return None
    total = sum(sum(c.values()) for c in chunks)
    return total / len(chunks) if total else None
