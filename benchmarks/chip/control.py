#!/usr/bin/env python3
"""Readings that set the correctness limits of a cell, on the chip.

    python3 benchmarks/chip/control.py --workload <cell> --seeds 1,2,... \
        [--control-seeds 21,22,23] [--seconds 2] [--faults]

In one process (set-up is long, and the compile cache is shared), it runs
the cell as ``run.py`` does, with a short window, once per seed of
``--seeds``: the sound readings of every number compared.  Then, once per
seed of ``--control-seeds``, the control:

- a served cell: the configuration's plain reference computed in bfloat16,
  put in the engine's place (after the build's gate);
- a training cell: the plain reference computed in bfloat16 in the
  program's place (its steps take no matrix unit pass that a precision
  setting would lower: the arithmetic is float32 elementwise work), read
  against the float32 reference;

and with ``--faults`` the cell's planted faults (training: half of the
batch left out; serving: answers altered; four chips: one device's shard
lost).  One JSON line per reading, and a summary: for each number, the
largest sound reading and the smallest reading of each control and fault.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


class Patch:
    """``setattr`` that remembers, and ``undo`` that restores."""

    def __init__(self):
        self._saved = []

    def setattr(self, obj, name, value):
        self._saved.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def undo(self):
        while self._saved:
            obj, name, value = self._saved.pop()
            setattr(obj, name, value)


def reading(harness, workload, seed, seconds, devices, kind, patch=None) -> dict:
    run = harness.Run(harness.load_bench(), workload, seed, seconds, False)
    run.devices = devices
    mp = Patch()
    try:
        if patch is not None:
            patch(mp, run)
        t0 = time.monotonic()
        res = harness.execute(run, t0)
    finally:
        mp.undo()
    out = {"kind": kind, "seed": seed, "correct": res["correct"],
           "checks": {k: v["value"] for k, v in res["checks"].items()},
           "metrics": {k: v["value"] for k, v in res["metrics"].items()},
           "wall_s": time.monotonic() - t0}
    print(json.dumps(out), flush=True)
    return out


def train_control(harness, run, seed: int) -> dict:
    """The training control: the plain reference computed in bfloat16, in
    the program's place, read against the float32 reference on the same
    weights and rows."""
    import jax.numpy as jnp

    import weights

    cfg, mix, model = run.cfg, run.traffic, run.model
    batch, n_batches = mix["batch"], mix["dataset_rows"] // mix["batch"]
    x, y = model.train_data(cfg, seed, n_batches * batch)
    p0 = weights.to_host(model.make_weights(cfg, seed, serve=False))
    rows = [slice(k % n_batches * batch, (k % n_batches + 1) * batch)
            for k in range(run.driver.checked_steps(mix))]
    batches = [(x[r], y[r]) for r in rows]
    ref = model.train_reference(cfg, p0, batches, mix["beta"], mix["adam"])
    low = model.train_reference(cfg, p0, batches, mix["beta"], mix["adam"],
                                dtype=jnp.bfloat16)
    checks = run.driver.compare(low[0], low[1], low[2], p0, ref)
    out = {"kind": "control_bf16", "seed": seed,
           "correct": all(v <= run.limits[k] for k, v in checks.items()),
           "checks": checks}
    print(json.dumps(out), flush=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--faults", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import ml_dtypes
    import numpy as np

    import faults
    import harness

    bench = harness.load_bench()
    devices = harness.require_chips(
        {w["name"]: w for w in bench["workloads"]}[args.workload]["chips"])
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    cseeds = [int(s) for s in args.control_seeds.split(",") if s]
    rows = [reading(harness, args.workload, s, args.seconds, devices, "sound")
            for s in seeds]
    probe = harness.Run(bench, args.workload, 0, 1.0, False)
    training = probe.traffic["driver"] == "train"
    kinds = []
    if training:
        rows += [train_control(harness, probe, s) for s in cseeds]
        if args.faults:
            kinds.append(("fault_half_batch",
                          lambda mp, run: faults.half_batch(mp)))
    else:
        def control(mp, run):
            params = {}
            make = run.model.make_weights

            def keep(*a, **k):
                params["p"] = make(*a, **k)
                return params["p"]

            mp.setattr(run.model, "make_weights", keep)

            class Lazy:
                ref = None

                def __call__(self, codes):
                    if self.ref is None:
                        self.ref = run.model.Reference(run.cfg, params["p"],
                                                       dtype=ml_dtypes.bfloat16)
                    return self.ref(codes)
            faults.control_engine(mp, Lazy())
        kinds.append(("control_bf16", control))
        if args.faults:
            kinds.append(("fault_altered", lambda mp, run: faults.altered_answers(
                mp, slice(0, None, 7))))
            if probe.chips > 1:
                kinds.append(("fault_lost_shard", lambda mp, run: faults.lost_shard(
                    mp, run.chips)))
    for kind, patch in kinds:
        for s in cseeds:
            rows.append(reading(harness, args.workload, s, args.seconds, devices,
                                kind, patch))
    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r["checks"] for r in rows if r["kind"] == kind]
        agg = np.max if kind == "sound" else np.min
        summary[kind] = {k: float(agg([c[k] for c in sel])) for k in sel[0]}
        summary[kind]["n"] = len(sel)
        summary[kind]["all_correct"] = all(r["correct"] for r in rows
                                           if r["kind"] == kind)
    print(json.dumps({"workload": args.workload, "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
