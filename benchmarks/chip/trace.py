"""Reduction of a profiler trace to device busy time, top ops and gaps.

The benchmark brackets its measured window in a host span named
``bench.window`` and each call into the program in ``bench.<what>``
spans (``jax.profiler.TraceAnnotation``).  From the trace:

- busy time of a device: the union of its op intervals inside the window;
  ``busy_s`` is the mean over the devices the cell uses;
- ``device_ops``: the ops that took most device time, summed by name over
  the devices;
- ``idle_gaps``: the time inside the window in which no op ran, summed by
  the innermost ``bench.*`` host span that was open at the gap's middle
  (``idle`` where none was).
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

Interval = Tuple[float, float, str]     # start s, end s, name

WINDOW_SPAN = "bench.window"
OP_LINES = ("XLA Ops",)                  # device lines whose events are ops
TOP = 10


def union(intervals: List[Interval], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged intervals clipped to ``[lo, hi]``."""
    spans = sorted((max(s, lo), min(e, hi)) for s, e, _ in intervals
                   if e > lo and s < hi)
    merged: List[Tuple[float, float]] = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], e))
        else:
            merged.append((s, e))
    return merged


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if at < hi:
        out.append((at, hi))
    return out


def _innermost(spans: List[Interval], t: float) -> str:
    best, width = "idle", float("inf")
    for s, e, name in spans:
        if s <= t <= e and name != WINDOW_SPAN and e - s < width:
            best, width = name, e - s
    return best


def reduce(devices: Dict[str, List[Interval]], host: List[Interval]
           ) -> dict:
    """``devices``: op intervals per device; ``host``: ``bench.*`` spans.
    Returns ``busy_s``, ``window_s``, per-device busy and the breakdown."""
    window = [(s, e) for s, e, n in host if n == WINDOW_SPAN]
    if not window:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = window[0]
    per_device, op_time, gap_time = {}, defaultdict(float), defaultdict(float)
    for dev, ops in devices.items():
        busy = union(ops, lo, hi)
        per_device[dev] = sum(e - s for s, e in busy)
        for s, e, name in ops:
            if e > lo and s < hi:
                op_time[name] += min(e, hi) - max(s, lo)
        for s, e in gaps(busy, lo, hi):
            gap_time[_innermost(host, (s + e) / 2)] += e - s
    n = max(len(devices), 1)
    for k in gap_time:
        gap_time[k] /= n
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": sum(per_device.values()) / n, "window_s": hi - lo,
            "per_device_busy_s": per_device,
            "breakdown": {"device_ops": top(op_time),
                          "idle_gaps": top(gap_time)}}


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``: a TPU trace
    names each op event by its HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(trace_dir: str, device_prefix: str, n_devices: int):
    """Op intervals of the first ``n_devices`` devices whose plane name
    starts with ``device_prefix``, and the ``bench.*`` host spans, from the
    newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    devices: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            ops = [(ev.start_ns * 1e-9, ev.end_ns * 1e-9, op_name(ev.name))
                   for line in plane.lines if line.name in OP_LINES
                   for ev in line.events]
            devices[plane.name] = ops
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9,
                                 ev.name))
    chosen = dict(sorted(devices.items())[:n_devices])
    return chosen, host
