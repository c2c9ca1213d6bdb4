"""The serve loop's own host records of the measured run, for the
readers of its spans and counters.

The program keeps the records of the latest serve run it finished
(`repro.launch.trace.last_run()`: self seconds by span per tick, each
microbatch's size, service time and BiBFS counters, construction's self
seconds). In a cell run that is the measured loop's: set-up's warm loop
finishes before it. A program that keeps no such record, or a record
whose ticks or answers differ from the window's, gives None, and the
metric is left out of the result line.
"""
import importlib


def of(window):
    try:
        trace = importlib.import_module("repro.launch.trace")
    except ImportError:
        return None
    last_run = getattr(trace, "last_run", None)
    rec = last_run() if callable(last_run) else None
    if rec is None or len(rec.host_s) != window.ticks \
            or sum(m.size for m in rec.microbatches) != window.answered:
        return None
    return rec
