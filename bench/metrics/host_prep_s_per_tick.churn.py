"""Host preparation per committed tick, in s: the mean over the window's
ticks of the summed self seconds of the serve loop's `serve.prepare.*`
spans (`TickStats.host_s`)."""
from benchlib import serverecord

PREFIX = "serve.prepare."


def read(run):
    rec = serverecord.of(run)
    if rec is None or not rec.host_s:
        return None
    return sum(v for h in rec.host_s for k, v in h.items()
               if k.startswith(PREFIX)) / len(rec.host_s)
