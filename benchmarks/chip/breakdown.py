#!/usr/bin/env python3
"""One cell's window read through the program's own spans and counters.

    python3 benchmarks/chip/breakdown.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Runs the cell as ``run.py`` does (set-up, window, check) and prints one
JSON line:

- ``e2e``: the end-to-end metrics, which ``run.py`` prints only untraced,
  so a traced and an untraced run of one seed give the cost of tracing;
- ``counters``: what the program counted over the window (``COUNTERS``),
  read after the window's clock has stopped; left out where the program
  keeps no such counter;
- ``per_layer``: the cell's per-layer metrics of ``BENCHMARK.json``
  (traced only);
- ``breakdown`` (traced only): ``spans.reduce`` of the window, its idle
  gaps by the innermost ``bench.*`` or ``hgq.*`` span, and its top
  device ops.

Like ``run.py`` it exits with code 2 where there is no TPU.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for _path in (HERE, os.path.join(ROOT, "src")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

import harness  # noqa: E402


class Chunks:
    """The train generator, summing each chunk's feed wait and dispatch."""

    def __init__(self, gen):
        self.gen, self.chunks, self.wait_s, self.dispatch_s = gen, 0, 0.0, 0.0

    def __iter__(self):
        return self

    def __next__(self):
        res = next(self.gen)
        self.chunks += 1
        self.wait_s += getattr(res, "wait_s", float("nan"))
        self.dispatch_s += getattr(res, "dispatch_s", float("nan"))
        return res

    def close(self) -> None:
        self.gen.close()


def snapshot(st: dict) -> dict:
    """The program's counters held in a cell's set-up state, and the collector's."""
    out = {}
    tier, engine, gen = st.get("tier"), st.get("engine"), st.get("gen")
    if tier is not None:
        s = tier.stats()
        out.update(tier_requests=s.n_requests, tier_batches=s.n_batches,
                   tier_queue_wait_s=getattr(s, "queue_wait_s", None),
                   tier_flush_s=getattr(s, "flush_s", None))
    if engine is not None:
        out.update(engine_calls=getattr(engine, "n_calls", None),
                   engine_place_s=getattr(engine, "place_s", None))
    if isinstance(gen, Chunks):
        out.update(train_chunks=gen.chunks, train_wait_s=gen.wait_s,
                   train_dispatch_s=gen.dispatch_s)
    try:
        from repro.obs import gc_stats
    except ImportError:
        return out
    out["gc_pause_s"] = gc_stats().pause_s
    return out


def delta(after: dict, before: dict) -> dict:
    return {k: (v - before[k] if v is not None and before.get(k) is not None else None)
            for k, v in after.items()}


def _per(num, den, scale=1e3):
    if num is None or not den or num != num:        # nan: not counted
        return None
    return num / den * scale


# per-layer quantities from the counters' deltas over the window; each is
# None where its counter was not kept or counted nothing
COUNTERS = {
    "tier_queue_ms.stream": lambda d: _per(d.get("tier_queue_wait_s"), d.get("tier_requests")),
    "tier_flush_ms.stream": lambda d: _per(d.get("tier_flush_s"), d.get("tier_batches")),
    "engine_place_ms.bulk": lambda d: _per(d.get("engine_place_s"), d.get("engine_calls")),
    "train_feed_wait_ms": lambda d: _per(d.get("train_wait_s"), d.get("train_chunks")),
    "train_dispatch_ms": lambda d: _per(d.get("train_dispatch_s"), d.get("train_chunks")),
    "gc_pause_ms": lambda d: _per(d.get("gc_pause_s"), 1),
}


def counter_metrics(d: dict) -> dict:
    """The ``COUNTERS`` that have a value."""
    return {k: v for k, f in COUNTERS.items() if (v := f(d)) is not None}


def execute(run, t_start: float, trace_dir: str = harness.TRACE_DIR) -> dict:
    import jax

    from work import peaks

    try:
        from repro.obs import watch_gc
        watch_gc()
    except ImportError:
        pass
    run.peak = peaks(run.devices[0].device_kind)
    run.t0 = t_start
    st = run.driver.setup(run)
    setup_s = time.monotonic() - t_start
    if "gen" in st:
        st["gen"] = Chunks(st["gen"])
    if run.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = snapshot(st)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            run.driver.window(run, st)
    finally:
        after = snapshot(st)
        if run.trace:
            jax.profiler.stop_trace()
    out = {"workload": run.workload["name"], "seed": run.seed,
           "trace": int(run.trace), "setup_s": setup_s, "e2e": dict(run.e2e),
           "counters": counter_metrics(delta(after, before))}
    if run.trace:
        import spans

        devs, host = spans.load(trace_dir, "/device:TPU:", run.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        run.traced = spans.reduce(devs, host, top=40)
        out["device"] = {k: run.traced[k] for k in ("busy_s", "window_s")}
        out["breakdown"] = run.traced["breakdown"]
        out["n_spans"] = len(host)
    run.driver.check(run, st)
    if run.trace:
        out["per_layer"] = {}
        for m in harness._metric_list(run.bench, "per_layer", run.workload["name"]):
            reader = harness.load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                                         "metric_" + m["name"].replace(".", "_"))
            out["per_layer"][m["name"]] = reader.read(run)
    checks = {k: {"value": v, "limit": run.limits[k]} for k, v in run.checks.items()}
    out["correct"] = (bool(checks) and set(run.limits) <= set(run.checks)
                      and all(c["value"] <= c["limit"] for c in checks.values()))
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    t_start = time.monotonic()
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    run = harness.Run(harness.load_bench(), args.workload, args.seed,
                      args.seconds, bool(args.trace))
    try:
        run.devices = harness.require_chips(run.chips)
    except harness.NoChip as e:
        print(f"[breakdown] {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    print(json.dumps(execute(run, t_start)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
