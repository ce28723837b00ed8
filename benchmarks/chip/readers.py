"""Shared arithmetic of the per-layer metric readers in ``metrics/``.

Each reader returns ``None`` where the run has nothing for it to read; a
share of a roofline or a peak is never reported as 0 for want of data.
"""

from __future__ import annotations

import numpy as np


def idle_share_pct(run):
    t = run.traced
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])


def mean_ms(run, span: str):
    v = run.spans.get(span)
    return float(np.mean(v)) * 1e3 if v else None


def busy_per(run, counter: str, scale: float):
    t, n = run.traced, run.counters.get(counter)
    if not t or not n or t["busy_s"] <= 0:
        return None
    return t["busy_s"] / n * scale
