"""Open-loop single-row requests through ``ServeTier``.

Arrivals are a Poisson process at the mix's fixed rate; each request is
one row of the configuration's inputs, submitted at its absolute due
instant (``pacing.drive``) and timed from that instant to the completion
of its future.  Every response due in the window is compared with the
plain reference.

The client keeps no future: each future's done-callback writes the
output row and the completion instant into preallocated arrays
(``Responses``), so a window's requests leave nothing behind for the
garbage collector.  A client that held every future (about eight objects
the collector tracks, each) made its full collections, 80 to 150 ms
each, set the latency tail.
"""

from __future__ import annotations

import functools
import time

import numpy as np

import pacing

WAIT_S = 60.0       # how long past the window's close a response may come


class Responses:
    """Outputs and completion instants of ``n`` requests, filled in by the
    done-callbacks of their futures."""

    def __init__(self, n: int, n_outputs: int):
        self.out = np.zeros((n, n_outputs), np.int64)
        self.done = np.full(n, np.nan)      # completion instant, if served
        self.finished = np.zeros(n, bool)   # served or failed

    def submit(self, tier, codes, k: int) -> None:
        tier.submit(codes).add_done_callback(functools.partial(self._finish, k))

    def _finish(self, k: int, fut) -> None:
        t = time.monotonic()
        if fut.exception() is None:
            self.out[k] = fut.result()
            self.done[k] = t
        self.finished[k] = True

    def count(self) -> int:
        return int(np.count_nonzero(self.finished))

    def wait(self, timeout: float) -> None:
        end = time.monotonic() + timeout
        while self.count() < len(self.finished) and time.monotonic() < end:
            time.sleep(0.005)


def setup(run):
    from repro.serve.api import EngineSpec, build, tier_from_built
    from repro.serve.scheduler import ServeConfig
    from repro.serve.tier import TierConfig

    from work import serve_work

    cfg, mix, model = run.cfg, run.traffic, run.model
    params = model.make_weights(cfg, run.seed, serve=True)
    run.mark("weights made")
    prog = model.lower(cfg, params)
    run.mark(f"lowered: {prog.n_instrs()} instructions")
    built = build(prog, EngineSpec())
    run.mark(f"built and gated: {built.timings}")
    tier = tier_from_built({"model": built}, TierConfig(
        n_replicas=mix["replicas"],
        serve=ServeConfig(max_batch=mix["max_batch"],
                          max_delay_ms=mix["max_delay_ms"])))
    run.mark("tier started, ladder warm")
    rate = mix["rate_per_s"]
    schedule = pacing.arrivals(run.seed, rate, run.seconds)
    warm = pacing.arrivals(run.seed + 1, rate, mix["warm_s"])
    codes = model.request_codes(cfg, run.seed, len(schedule) + len(warm))
    st = {"tier": tier, "params": params, "codes": codes[len(warm):],
          "schedule": schedule, "n_outputs": built.engine.n_outputs}
    resp = Responses(len(warm), st["n_outputs"])
    pacing.drive(lambda k: resp.submit(tier, codes[k], k), range(len(warm)), warm)
    resp.wait(WAIT_S)
    run.mark("warm traffic served")
    run.work = serve_work(prog)
    st["stats0"] = tier.stats()
    return st


def window(run, st) -> None:
    tier, codes, n = st["tier"], st["codes"], len(st["schedule"])
    resp = Responses(n, st["n_outputs"])
    t0, sent, _ = pacing.drive(lambda k: resp.submit(tier, codes[k], k),
                               range(n), st["schedule"])
    resp.wait(WAIT_S)
    stats = tier.stats()
    due = t0 + st["schedule"]
    lat = pacing.latency_ms(due, resp.done)
    run.e2e["serve_p50_ms"] = pacing.percentile(lat, 50)
    run.spans["gen_late_ms"] = list((sent - due) * 1e3)
    run.counters.update(
        requests=n,
        tier_requests=stats.n_requests - st["stats0"].n_requests,
        tier_batches=stats.n_batches - st["stats0"].n_batches)
    st["resp"] = resp


def check(run, st) -> None:
    st["tier"].stop()
    resp, codes = st["resp"], st["codes"]
    ok = np.flatnonzero(np.isfinite(resp.done))
    lo, hi = run.model.Reference(run.cfg, st["params"])(codes[ok])
    out = resp.out[ok]
    wrong = np.any((out < lo) | (out > hi), axis=1)
    run.attempted = len(resp.done)
    run.failed = run.attempted - len(ok)
    run.checks["missing"] = run.failed
    run.checks["wrong_rows"] = int(wrong.sum())
