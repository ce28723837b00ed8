"""Runs one cell of ``BENCHMARK.json`` and prints its result line.

Everything that belongs to one configuration, traffic mix, per-layer
metric or cell is found by name:

- ``configs/<config>.json`` (sizes) and ``configs/<config>.py`` (how the
  benchmark builds it through the program's entry points, and its plain
  reference);
- ``traffic/<traffic>.json`` (parameters of the mix), whose ``driver``
  names the general generator in ``drivers/<driver>.py``;
- ``metrics/<metric>.py`` (a reader of one per-layer metric);
- ``limits/<cell>.json`` (the limit of each number the correctness
  comparison reads).

A driver has three steps: ``setup(run)`` (everything before the window,
timed as ``setup_s``), ``window(run, state)`` (the measured window) and
``check(run, state)`` (the comparison with the reference, after the
window has closed and device memory has been read).
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_bench(root: str = ROOT) -> dict:
    return _json(os.path.join(root, "BENCHMARK.json"))


class Run:
    """One run of one cell: its inputs, and what the driver records."""

    def __init__(self, bench: dict, workload: str, seed: int, seconds: float,
                 trace: bool, *, root: str = ROOT, overrides: Optional[dict] = None):
        by_name = {w["name"]: w for w in bench["workloads"]}
        if workload not in by_name:
            raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                           f"(have {sorted(by_name)})")
        self.workload = by_name[workload]
        conf = {c["name"]: c for c in bench["configs"]}[self.workload["config"]]
        overrides = overrides or {}
        self.cfg = {**_json(os.path.join(root, conf["file"])),
                    **overrides.get("config", {})}
        self.traffic = {**_json(os.path.join(HERE, "traffic",
                                             self.workload["traffic"] + ".json")),
                        **overrides.get("traffic", {})}
        self.limits = _json(os.path.join(HERE, "limits", workload + ".json"))
        self.model = load_module(os.path.join(HERE, "configs", conf["name"] + ".py"),
                                 "config_" + conf["name"].replace("-", "_"))
        self.driver = load_module(
            os.path.join(HERE, "drivers", self.traffic["driver"] + ".py"),
            "driver_" + self.traffic["driver"])
        self.bench, self.seed, self.seconds, self.trace = bench, seed, seconds, trace
        self.chips = int(self.workload["chips"])
        self.devices: List = []
        self.peak: dict = {}
        self.e2e: Dict[str, float] = {}          # end-to-end metrics
        self.counters: Dict[str, float] = {}     # counts over the window
        self.spans: Dict[str, list] = {}         # host-clock spans, per name
        self.work: dict = {}                     # work.py counts
        self.traced: dict = {}                   # trace.reduce of the window
        self.checks: Dict[str, float] = {}       # numbers compared
        self.attempted = 0
        self.failed = 0
        self.t0 = time.monotonic()

    def mark(self, stage: str) -> None:
        """Log a set-up stage with the seconds since the run began."""
        print(f"[bench] +{time.monotonic() - self.t0:.2f}s {stage}",
              file=sys.stderr, flush=True)


def require_chips(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform!r} devices")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else a fixed directory in the checkout; every program is cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _metric_list(bench: dict, kind: str, workload: str) -> List[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def execute(run: Run, t_start: float) -> dict:
    """Set-up, window, check; returns the result object (not printed)."""
    import jax

    from work import peaks

    run.peak = peaks(run.devices[0].device_kind)
    run.t0 = t_start
    run.mark("devices found")
    state = run.driver.setup(run)
    setup_s = time.monotonic() - t_start
    run.mark("set-up done")
    if run.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # no Python-call tracer: on the host-bound cells it would slow the
        # very path the trace is read for; the bench.* spans stay
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            run.driver.window(run, state)
    finally:
        if run.trace:
            jax.profiler.stop_trace()
    used = run.devices[:run.chips]
    stats = [d.memory_stats() or {} for d in used]
    device = {"platform": run.devices[0].platform,
              "kind": run.devices[0].device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": max(int(s.get("peak_bytes_in_use", 0))
                                       for s in stats)}
    breakdown = None
    if run.trace:
        # by path: the standard library has a module named ``trace``
        reducer = load_module(os.path.join(HERE, "trace.py"), "bench_trace")
        devs, host = reducer.load(TRACE_DIR, "/device:TPU:", run.chips)
        run.traced = reducer.reduce(devs, host)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = run.traced["busy_s"]
        device["window_s"] = run.traced["window_s"]
        breakdown = run.traced["breakdown"]
    run.mark("window done")
    run.driver.check(run, state)
    run.mark("check done")
    del state
    return assemble(run, setup_s, device, breakdown)


def assemble(run: Run, setup_s: float, device: dict,
             breakdown: Optional[dict]) -> dict:
    name = run.workload["name"]
    metrics = {}
    if run.trace:
        for m in _metric_list(run.bench, "per_layer", name):
            reader = load_module(os.path.join(HERE, "metrics", m["name"] + ".py"),
                                 "metric_" + m["name"].replace(".", "_"))
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in _metric_list(run.bench, "end_to_end", name):
            value = setup_s if m["name"] == "setup_s" else run.e2e.get(m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {k: {"value": v, "limit": run.limits[k]}
              for k, v in run.checks.items()}
    correct = (bool(checks) and run.attempted > 0
               and set(run.limits) <= set(run.checks)
               and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    t_start = time.monotonic()
    import argparse

    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")
    for path in (os.path.join(ROOT, "src"), HERE):
        if path not in sys.path:
            sys.path.insert(0, path)
    run = Run(load_bench(), args.workload, args.seed, args.seconds,
              bool(args.trace))
    try:
        run.devices = require_chips(run.chips)
    except NoChip as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    result = execute(run, t_start)
    print(json.dumps(result))
    for k, c in result["checks"].items():
        print(f"[bench] check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0
