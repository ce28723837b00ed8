"""Python's garbage collector on the profiler's clock.

A full collection over a serving process's heap pauses every thread for
tens of milliseconds, which from outside looks the same as a queue that
has run past its knee.  :func:`watch_gc` installs one ``gc.callbacks``
hook that opens an ``hgq.gc`` host span over every collector pass, so a
profiler trace puts such a pause beside the device ops, and counts passes
and seconds per generation at all times; :func:`gc_stats` reads them.

The program's other spans (``hgq.tier.*``, ``hgq.engine.*``,
``hgq.train.*``, ``hgq.prefetch.*``) are opened where the work happens;
``docs/serving.md`` lists them all.
"""

from __future__ import annotations

import dataclasses
import gc
import time
from typing import Optional, Tuple

from jax.profiler import TraceAnnotation

GC_SPAN = "hgq.gc"


@dataclasses.dataclass(frozen=True)
class GcStats:
    """Collector passes and their seconds, per generation (0, 1, 2)."""

    passes: Tuple[int, ...] = (0, 0, 0)
    seconds: Tuple[float, ...] = (0.0, 0.0, 0.0)

    @property
    def pause_s(self) -> float:
        """Seconds the collector ran, all generations."""
        return sum(self.seconds)


class _GcWatch:
    """The ``gc.callbacks`` hook.  The interpreter runs one collection at a
    time, start to stop in one thread, so the hook is the only writer; each
    generation's total is replaced by one store, so a reader on another
    thread sees whole passes."""

    def __init__(self):
        self._totals = [(0, 0.0)] * 3          # (passes, seconds) by generation
        self._t0 = 0.0
        self._span: Optional[TraceAnnotation] = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._span = TraceAnnotation(GC_SPAN)
            self._span.__enter__()
            self._t0 = time.perf_counter()
            return
        dt = time.perf_counter() - self._t0
        span, self._span = self._span, None
        if span is not None:
            span.__exit__(None, None, None)
        gen = info["generation"]
        n, s = self._totals[gen]
        self._totals[gen] = (n + 1, s + dt)

    def stats(self) -> GcStats:
        totals = list(self._totals)
        return GcStats(passes=tuple(n for n, _ in totals),
                       seconds=tuple(s for _, s in totals))


_WATCH = _GcWatch()


def watch_gc() -> None:
    """Install the collector hook; calling it again changes nothing."""
    if _WATCH not in gc.callbacks:
        gc.callbacks.append(_WATCH)


def gc_stats() -> GcStats:
    """Passes and seconds counted since :func:`watch_gc` was first called."""
    return _WATCH.stats()
