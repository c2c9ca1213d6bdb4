"""Share of the traced window in which no operation ran on the chip
(1 - union of device-operation intervals / window), in %."""


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
