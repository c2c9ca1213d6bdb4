"""Share of the answered microbatches whose BiBFS ran the bit-packed
unit-weight path (one packed-word OR sweep per wave) rather than
Bellman-Ford waves, in %. A program that does not record the path
gives nothing."""
from benchlib import serverecord


def read(run):
    rec = serverecord.of(run)
    paths = [getattr(m, "bit_packed", None) for m in rec.microbatches] \
        if rec else []
    if not paths or None in paths:
        return None
    return 100.0 * sum(paths) / len(paths)
