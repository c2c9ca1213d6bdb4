"""BiBFS waves per query microbatch: the mean over the window's
microbatches of the `bounded_bibfs` loop's trip count."""
from benchlib import serverecord


def read(run):
    rec = serverecord.of(run)
    waves = [m.waves for m in rec.microbatches] if rec else []
    if not waves or None in waves:
        return None
    return sum(waves) / len(waves)
