#!/usr/bin/env python3
"""Compile each cell's programs for a described TPU v5e, on a host with no chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [--cells a,b] \
        [--n-samples 3000]

For every cell of ``BENCHMARK.json`` it builds the configuration as the
cell's driver does (weights from seed 0, the program's lowering) and
compiles, with the TPU compiler against a described ``v5e:2x2`` topology,
every shape the cell's window and set-up run: the serve engine at the
tier's batch ladder and the gate's batch, or at the bulk batch over the
cell's chips; the chunked train step at each chunk length.  It prints one
JSON object of compile seconds per cell and shape.  Nothing runs, so
nothing is timed but the compiler; what the compiler refuses here would
fail on the chip.  ``--n-samples`` overrides the PID context, to choose it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
GATE_ROWS = 1024     # serve.api.build's default gate batch


def _timed(fn) -> float:
    t0 = time.monotonic()
    fn()
    return round(time.monotonic() - t0, 3)


def serve_shapes(run, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.kernels.lut_serve import compile_program
    from repro.launch.mesh import make_mesh
    from repro.serve.scheduler import bucket_ladder

    cfg, mix = run.cfg, run.traffic
    t0 = time.monotonic()
    prog = run.model.lower(cfg, run.model.make_weights(cfg, 0, serve=True))
    out = {"lower_s": round(time.monotonic() - t0, 3), "n_instrs": prog.n_instrs()}
    mesh = make_mesh((run.chips,), ("data",), devices=topo.devices[:run.chips])
    if mix["driver"] == "open_loop":
        mesh = make_mesh((1,), ("data",), devices=topo.devices[:1])
        batches = bucket_ladder(mix["max_batch"]) + [GATE_ROWS]
        engine_mesh = None
    else:
        batches = [mix["rows_per_device"] * run.chips, GATE_ROWS]
        engine_mesh = mesh
    engine = compile_program(prog, mesh=engine_mesh, jit=False)
    out["path"] = engine.path
    sharding = NamedSharding(mesh, P("data", None))
    for b in batches:
        x = jax.ShapeDtypeStruct((b, engine.n_inputs), jnp.dtype(engine.dtype),
                                 sharding=sharding)
        out[f"compile_s.b{b}"] = _timed(
            lambda: jax.jit(engine._runner).lower(x).compile())
    return out


def train_shapes(run, topo) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.core.ebops import BetaSchedule
    from repro.optim.adam import AdamConfig, adam_init
    from repro.train.loop import make_chunked_step
    from repro.train.steps import TrainHParams, make_lut_train_step

    cfg, mix = run.cfg, run.traffic
    one = SingleDeviceSharding(topo.devices[0])
    beta, adam = mix["beta"], mix["adam"]
    hp = TrainHParams(adam=AdamConfig(**adam),
                      beta=BetaSchedule(beta["init"], beta["final"], beta["steps"]))
    step_fn, _ = make_lut_train_step(run.model.layers(cfg), hp, jit=False)
    params = jax.eval_shape(lambda: run.model.make_weights(cfg, 0, serve=False))
    state = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
                         (params, jax.eval_shape(adam_init, params)))
    chunk = make_chunked_step(step_fn)
    out = {}
    for k in sorted({1, mix["chunk_steps"]}):
        batches = {"x": jax.ShapeDtypeStruct((k, mix["batch"], cfg["dims"][0]),
                                             jnp.float32, sharding=one),
                   "y": jax.ShapeDtypeStruct((k, mix["batch"]), jnp.int32,
                                             sharding=one)}
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            out[f"compile_s.k{k}"] = _timed(
                lambda: chunk.lower(*state, batches).compile())
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cells", default="", help="comma-separated cells (default all)")
    ap.add_argument("--n-samples", type=int, default=0,
                    help="override the PID context length")
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [os.path.join(ROOT, "src"), HERE]
    import jax
    from jax.experimental import topologies

    import harness

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    bench = harness.load_bench()
    names = [c for c in args.cells.split(",") if c] or \
        [w["name"] for w in bench["workloads"]]
    report = {}
    for name in names:
        run = harness.Run(bench, name, 0, 1.0, False)
        if args.n_samples and "n_samples" in run.cfg:
            run.cfg["n_samples"] = args.n_samples
        fn = train_shapes if run.traffic["driver"] == "train" else serve_shapes
        report[name] = fn(run, topo)
        print(json.dumps({name: report[name]}), flush=True)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
