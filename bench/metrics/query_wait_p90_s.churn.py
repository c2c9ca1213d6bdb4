"""90th percentile over the window's answers of the time each query
waited before its microbatch was dispatched, in s: its latency (due
time -> answer) less its microbatch's `service_s` (the serve loop's
`serve.microbatch` span, dispatch -> answer)."""
import numpy as np

from benchlib import serverecord


def read(run):
    rec = serverecord.of(run)
    if rec is None or not rec.microbatches:
        return None
    service = np.repeat([m.service_s for m in rec.microbatches],
                        [m.size for m in rec.microbatches])
    return float(np.percentile(run.latencies - service, 90))
