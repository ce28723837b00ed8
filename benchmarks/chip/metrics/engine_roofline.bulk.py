"""Engine: the least time the window's rows need on the cell's chips
(``work.least_time_s``: the larger of integer ops over the int8 peak and
code bytes over HBM bandwidth) over the device busy time (trace), in %."""

from work import least_time_s


def read(run):
    t, rows = run.traced, run.counters.get("rows")
    if not t or not rows or t["busy_s"] <= 0:
        return None
    return 100.0 * least_time_s(run.work, rows, run.peak, run.chips) / t["busy_s"]
