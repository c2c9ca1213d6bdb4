"""Host spans of the serve loop, in memory and on the profiler's timeline.

`SpanRecorder.span(name, **ids)` is a context manager that does two
things:

* it enters `jax.profiler.TraceAnnotation(name, **ids)`, so that while a
  profile is being taken the span lands on the profiler's host timeline
  next to the device events. That timeline is on the wall clock: an
  event's start is the xplane's `profile_start_time` plus its offset;
* it records start and end with `time.time_ns()`, the same clock, and
  keeps a stack of open spans, so that each span's *self time* is its
  duration less the time of the spans opened inside it.

Self seconds are summed by span name until `take()` returns them and
clears the raw span list; the serve loop takes once per tick, so memory
does not grow with the run. There is no switch: when no profile runs, a
span costs a few microseconds of host time.

A finished serve run `publish`es its host records (`RunRecord`: no
device arrays) and `last_run()` returns the latest in the process, for
readers that see the run only after its loop and report are freed.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import time
from typing import NamedTuple

import jax


@dataclasses.dataclass
class Span:
    """One closed (or still open) span; times in `time.time_ns()`."""
    name: str
    ids: dict
    start_ns: int
    end_ns: int = 0
    child_ns: int = 0
    annotation: jax.profiler.TraceAnnotation | None = dataclasses.field(
        default=None, repr=False)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9

    @property
    def self_ns(self) -> int:
        return self.end_ns - self.start_ns - self.child_ns

    def set(self, **ids) -> None:
        """Attach attributes known only once the span is open (e.g. the
        tag a chunk generator yields at its end)."""
        self.ids.update(ids)
        self.annotation.set_metadata(**ids)


class SpanRecorder:
    """The serve loop's spans: a stack of open ones, the closed ones of
    the current tick, and their self seconds summed by name."""

    def __init__(self):
        self.spans: list[Span] = []     # closed since the last take()
        self._open: list[Span] = []
        self._self_s: dict[str, float] = collections.defaultdict(float)

    @contextlib.contextmanager
    def span(self, name: str, **ids):
        with jax.profiler.TraceAnnotation(name, **ids) as annotation:
            sp = Span(name, dict(ids), time.time_ns(),
                      annotation=annotation)
            self._open.append(sp)
            try:
                yield sp
            finally:
                sp.end_ns = time.time_ns()
                self._open.pop()
                if self._open:
                    self._open[-1].child_ns += sp.end_ns - sp.start_ns
                self.spans.append(sp)
                self._self_s[name] += sp.self_ns / 1e9

    def seconds(self, prefix: str) -> float:
        """Self seconds, since the last take(), of the closed spans whose
        name starts with `prefix`."""
        return sum(v for k, v in self._self_s.items()
                   if k.startswith(prefix))

    def take(self) -> dict[str, float]:
        """Self seconds by span name since the last call; clears the
        closed spans."""
        out = dict(self._self_s)
        self._self_s.clear()
        self.spans.clear()
        return out


class MicrobatchHost(NamedTuple):
    """One answered microbatch's host record."""
    size: int                       # real (unpadded) queries
    service_s: float                # dispatch -> answer
    waves: int | None               # BiBFS waves (None on the mesh path)
    live_lane_waves: int | None     # waves the real lanes could improve in
    bit_packed: bool | None = None  # the BiBFS took the unit-weight path
    between_chunks: bool = False    # dispatched inside a pipelined update


@dataclasses.dataclass(frozen=True)
class RunRecord:
    """The host records of one finished serve run."""
    host_s: tuple[dict[str, float], ...]        # per committed tick
    microbatches: tuple[MicrobatchHost, ...]    # in answer order
    construct_s: dict[str, float]               # by serve.construct.* span
    #: per committed tick, pipelined update dispatches by phase tag
    #: (empty dicts on the sync path)
    update_chunks: tuple[dict[str, int], ...] = ()
    #: per committed tick, the update's fixpoint waves by phase
    #: ({"search": n, "repair": n}; empty on the mesh sync path)
    update_waves: tuple[dict[str, int], ...] = ()
    #: per committed tick, the sweep-stream sets the update built
    stream_builds: tuple[int, ...] = ()


_last_run: RunRecord | None = None


def publish(record: RunRecord) -> None:
    global _last_run
    _last_run = record


def last_run() -> RunRecord | None:
    """The records of the latest serve run this process finished."""
    return _last_run
