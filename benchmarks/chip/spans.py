"""Program spans in a profiler trace: idle gaps by the innermost open span.

``trace.py`` attributes the device's idle gaps to the benchmark's own
``bench.*`` spans.  The program opens ``hgq.*`` spans where its work
happens (the serving tier's replica threads, the engine, the train loop,
the prefetch worker, the garbage collector; ``docs/serving.md`` lists
them).  This module reads both:

- ``innermost``: for each instant, the shortest span that contains it,
  ``bench.window`` left out, ``idle`` where none does; the rule of
  ``trace.py``, as one sweep over the sorted spans (a stream's window
  holds tens of thousands of spans and thousands of gaps).  A TPU host's
  trace puts the spans of every Python thread on one line, so the
  shortest span may belong to a thread that merely waits;
- ``load``: ``trace.load`` with the spans of both prefixes;
- ``reduce``: ``trace.reduce``'s busy time, window and top ops, with every
  idle gap attributed by ``innermost`` over both prefixes.

A TPU op event is named by its HLO instruction without the instruction's
metadata, so the name scopes the program gives its stages and layers
(``stage<i>_<kind>``, ``l<i>_<Layer>``) are not in the trace: op names
stay as ``trace.py`` gives them.
"""

from __future__ import annotations

import glob
import heapq
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence

from harness import HERE, load_module

bench_trace = load_module(os.path.join(HERE, "trace.py"), "bench_trace")
Interval = bench_trace.Interval

PREFIXES = ("bench.", "hgq.")


def innermost(spans: Sequence[Interval], points: Sequence[float]) -> List[str]:
    """Name of the shortest span with ``start <= t <= end`` for each point
    (the earlier span on a tie), as ``trace._innermost`` finds it."""
    order = sorted((s, i) for i, (s, e, n) in enumerate(spans)
                   if n != bench_trace.WINDOW_SPAN)
    out: List[Optional[str]] = [None] * len(points)
    active: list = []                    # heap of (width, index, end)
    at = 0
    for k in sorted(range(len(points)), key=points.__getitem__):
        t = points[k]
        while at < len(order) and order[at][0] <= t:
            i = order[at][1]
            s, e, _ = spans[i]
            heapq.heappush(active, (e - s, i, e))
            at += 1
        while active and active[0][2] < t:
            heapq.heappop(active)        # ended before t: never again open
        out[k] = spans[active[0][1]][2] if active else "idle"
    return out  # type: ignore[return-value]


def load(trace_dir: str, device_prefix: str, n_devices: int):
    """As ``trace.load``, with the ``hgq.*`` spans beside the ``bench.*``."""
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
            devices[plane.name] = [
                (ev.start_ns * 1e-9, ev.end_ns * 1e-9, bench_trace.op_name(ev.name))
                for line in plane.lines if line.name in bench_trace.OP_LINES
                for ev in line.events]
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    host.append((ev.start_ns * 1e-9, ev.end_ns * 1e-9, ev.name))
    return dict(sorted(devices.items())[:n_devices]), host


def idle_gaps(devices: Dict[str, List[Interval]], spans: List[Interval],
              lo: float, hi: float, top: int) -> list:
    """Idle time in ``[lo, hi]`` by the innermost of ``spans`` open at each
    gap's middle, the ``top`` largest, mean over the devices."""
    gap_time: Dict[str, float] = defaultdict(float)
    for ops in devices.values():
        gaps = bench_trace.gaps(bench_trace.union(ops, lo, hi), lo, hi)
        for (s, e), name in zip(gaps, innermost(spans, [(s + e) / 2 for s, e in gaps])):
            gap_time[name] += e - s
    n = max(len(devices), 1)
    return [[k, v / n] for k, v in sorted(gap_time.items(), key=lambda kv: -kv[1])[:top]]


def reduce(devices: Dict[str, List[Interval]], host: List[Interval],
           top: int = bench_trace.TOP) -> dict:
    """``trace.reduce`` of the same trace, its idle gaps attributed over
    every span in ``host``."""
    window = [h for h in host if h[2] == bench_trace.WINDOW_SPAN]
    out = bench_trace.reduce(devices, window)
    out["breakdown"]["idle_gaps"] = idle_gaps(devices, host, window[0][0],
                                              window[0][1], top)
    return out
