"""Open-loop arrivals, absolute-deadline pacing and latency from due time.

The pacing is a copy of the program's ``serve/scheduler.drive_open_loop``:
every arrival instant is fixed up front and the client sleeps to that
absolute instant, so sleep overshoot never lowers the offered rate; a
late client catches up with a burst.  Unlike the program's tier counters,
latency is measured from when a request was *due*, so a stalled client
or server shows in the tail of every request queued behind the stall.
"""

from __future__ import annotations

import time
from typing import Callable, List, Sequence

import numpy as np

MISS_MS = 60_000.0   # latency charged to a request that failed or never came


def arrivals(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Poisson arrival offsets (s) of exactly ``round(rate * seconds)``
    requests in ``[0, seconds)``: given their count, the arrival times of a
    Poisson process are sorted uniform draws, so every seed offers the same
    amount of work."""
    n = int(round(rate * seconds))
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    return np.sort(rng.uniform(0.0, seconds, n))


def drive(submit: Callable, rows: Sequence, schedule: np.ndarray, *,
          clock: Callable[[], float] = time.monotonic,
          sleep: Callable[[float], None] = time.sleep):
    """Submit ``rows[k]`` at ``t0 + schedule[k]``.

    Returns ``(t0, sent, futures)``: the schedule's origin on ``clock``,
    the instant each submit was called, and what each submit returned.
    """
    sent = np.empty(len(schedule))
    futures: List = []
    t0 = clock()
    for k, at in enumerate(schedule):
        delay = t0 + at - clock()
        if delay > 0:
            sleep(delay)
        sent[k] = clock()
        futures.append(submit(rows[k]))
    return t0, sent, futures


def latency_ms(due: np.ndarray, done: np.ndarray) -> np.ndarray:
    """Milliseconds from due to done; a request with no completion
    (``nan``) counts as a miss of ``MISS_MS``."""
    out = (np.asarray(done, np.float64) - np.asarray(due, np.float64)) * 1e3
    return np.where(np.isfinite(out), out, MISS_MS)


def percentile(values: np.ndarray, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))
