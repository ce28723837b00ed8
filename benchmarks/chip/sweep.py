#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate the tier sustains.

    python3 benchmarks/chip/sweep.py --workload jsc-stream \
        --rates 6000,8000,10000,12000,14000,16000 --seconds 10 [--seed 0]

Run on the chip, in one process: it sets the cell up as its driver does,
then offers each rate in turn (Poisson arrivals, absolute-deadline
pacing) for ``--seconds`` and reports, per rate, the achieved submit
rate, the completion rate, the backlog (submitted and not yet completed)
at each quarter of the window, and the p50 and p99 latency from due
time.  A rate is sustained when the client submits at it (at least 98% of
the offered rate), at least 98% of the requests have completed by the
window's close, the backlog at the close is no larger than at the first
quarter plus what 10 ms of arrivals would leave, and the p99 latency
from due time is within twice that of the sweep's first (lowest) rate:
client and tier share one process, and once it saturates, requests wait
in the client, behind schedule, where no backlog counter sees them.  The
knee is the highest sustained rate below the first that is not; the
cell's ``rate_per_s`` is set to 0.8 of it, by hand, in its traffic file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def offer(tier, codes, schedule, n_outputs: int) -> dict:
    import pacing
    from open_loop import Responses

    n = len(schedule)
    resp = Responses(n, n_outputs)
    marks = iter([schedule[-1] * q / 4 for q in (1, 2, 3)])
    nxt, backlog = next(marks), []

    def submit(k: int) -> None:
        nonlocal nxt
        if nxt is not None and schedule[k] >= nxt:
            backlog.append(k - resp.count())
            nxt = next(marks, None)
        resp.submit(tier, codes[k % len(codes)], k)

    t0, sent, _ = pacing.drive(submit, range(n), schedule)
    t_end = time.monotonic()
    backlog.append(n - resp.count())
    resp.wait(120)
    done = resp.done
    lat = pacing.latency_ms(t0 + schedule, done)
    last = np.nanmax(done) - t0
    return {"offered": n / schedule[-1], "achieved_submit": n / (t_end - t0),
            "completed_per_s": n / last,
            "completed_by_close": float(np.mean(done <= t_end)),
            "backlog_quarters": backlog,
            "p50_ms": pacing.percentile(lat, 50),
            "p99_ms": pacing.percentile(lat, 99),
            "gen_late_p99_ms": pacing.percentile((sent - t0 - schedule) * 1e3, 99)}


def sustained(r: dict, base_p99_ms: float) -> bool:
    q = r["backlog_quarters"]
    return (r["achieved_submit"] >= 0.98 * r["offered"]
            and r["completed_by_close"] >= 0.98
            and q[-1] <= q[0] + 0.01 * r["offered"]
            and r["p99_ms"] <= 2.0 * base_p99_ms)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="jsc-stream")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE, os.path.join(HERE, "drivers")]
    import harness
    import pacing

    run = harness.Run(harness.load_bench(), args.workload, args.seed,
                      args.seconds, False)
    run.devices = harness.require_chips(run.chips)
    harness.enable_compile_cache()
    st = run.driver.setup(run)
    tier = st["tier"]
    codes = run.model.request_codes(run.cfg, args.seed, 200_000)
    rows, knee = [], None
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            r = offer(tier, codes, pacing.arrivals(args.seed, rate, args.seconds),
                      st["n_outputs"])
            r["sustained"] = bool(sustained(r, (rows or [r])[0]["p99_ms"]))
            rows.append(r)
            print(json.dumps(r), flush=True)
            if not r["sustained"]:
                break
            knee = rate
    finally:
        tier.stop()
    print(json.dumps({"knee_per_s": knee, "rates": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
