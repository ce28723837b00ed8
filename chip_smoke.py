#!/usr/bin/env python3
"""Bring-up check: the train -> lower -> serve path on a TPU.

Run from the repository root on a machine with a TPU::

    python chip_smoke.py              # one chip, every phase below
    python chip_smoke.py --chips 4    # four chips, only the mesh path

One chip.  The JSC-HLF LUT-Dense 16->20->5 model (hidden 8, BN on layer 0)
trains a few scan chunks (``train/loop.chunked_train``) at the paper's
batch of 16600, once on the einsum path and once with the fused Pallas
kernels; the losses must be finite and the first-step losses must agree.
The einsum-trained weights are lowered to a DAIS program (the verifier
runs) and built twice through ``serve.api.build``: on the fused engine and
on the Pallas mega-kernel, each gated bit-exact against
``DaisProgram.run``.  Each engine then serves 1024 single-row requests
through a ``ServeTier``, and every response is checked against the
interpreter.  The CEPC PID hybrid at ``ctx=100`` (random weights from the
seed) goes through the same build, gate and tier on both engines.

Four chips.  Both engines are built on a mesh over all four devices, a
batch runs through each (its output must be sharded across the four
devices and bit-exact), and a four-replica tier serves requests.

The script drives everything in this one process.  It exits non-zero and
prints no result when the first JAX device is not a TPU or any phase
fails; on success its last line is the JSON result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

JSC_DIMS = (16, 20, 5)
JSC_HIDDEN = 8
JSC_BATCH = 16600          # the paper's JSC training batch
IN_F, IN_I = 4, 3          # JSC input grid, as launch/pareto.py
PID_CTX = 100              # waveform context of benchmarks/serve_bench.py
N_REQUESTS = 1024
# fused == einsum forward tolerance of tests/test_lut_layers.py
LOSS_ATOL = 1e-5


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# --------------------------------------------------------------------------- #
# phases (CPU tests drive them at tiny sizes)
# --------------------------------------------------------------------------- #
def jsc_layers(dims=JSC_DIMS, hidden=JSC_HIDDEN, *, fused=False):
    from repro.core.lut_layers import LUTDense
    return [LUTDense(ci, co, hidden=hidden, use_batchnorm=(k == 0),
                     use_fused=fused)
            for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:]))]


def train_phase(*, fused: bool, seed: int = 0, batch: int = JSC_BATCH,
                steps: int = 8, chunk_steps: int = 4, dims=JSC_DIMS,
                hidden: int = JSC_HIDDEN) -> dict:
    """A few scan chunks of ``make_lut_train_step`` on ``jsc_hlf`` data.

    Matmuls run at float32 precision so the einsum and fused paths compute
    the same arithmetic and their losses can be compared.
    """
    import jax

    from repro.data.synthetic import jsc_hlf
    from repro.train.loop import chunked_train
    from repro.train.steps import TrainHParams, make_lut_train_step

    layers = jsc_layers(dims, hidden, fused=fused)
    step_fn, init_fn = make_lut_train_step(
        layers, TrainHParams(lut_use_fused=fused), jit=False)
    params, opt = init_fn(jax.random.PRNGKey(seed))
    x, y = jsc_hlf(seed, batch * steps)

    def get_batch(step: int) -> dict:
        rows = slice(step * batch, (step + 1) * batch)
        return {"x": x[rows], "y": y[rows]}

    losses, chunk_s = [], []
    with jax.default_matmul_precision("float32"):
        for res in chunked_train(step_fn, params, opt, get_batch, 0, steps,
                                 chunk_steps=chunk_steps):
            params = res.params
            losses += [float(v) for v in res.metrics["loss"]]
            chunk_s.append(res.dt_s)
    if not np.all(np.isfinite(losses)):
        raise RuntimeError(f"non-finite training loss: {losses}")
    out = {"losses": losses, "params": [params[f"l{k}"]
                                        for k in range(len(layers))],
           "layers": layers, "first_chunk_s": chunk_s[0],
           "last_chunk_s": chunk_s[-1],
           "fused_layers": [k for k, l in enumerate(layers)
                            if l.runs_fused(train=True)]}
    log(f"train {'fused' if fused else 'einsum'}: batch {batch}, {steps} "
        f"steps in chunks of {chunk_steps}, layers on the Pallas path "
        f"{out['fused_layers']}, first chunk (compile-inclusive) "
        f"{chunk_s[0]:.2f}s, last chunk {chunk_s[-1]:.3f}s, "
        f"losses {[round(v, 6) for v in losses]}")
    return out


def compare_losses(einsum: dict, fused: dict) -> float:
    """First-step losses of the two train paths must agree."""
    a, b = einsum["losses"][0], fused["losses"][0]
    if not abs(a - b) <= LOSS_ATOL:
        raise RuntimeError(f"first-step loss einsum {a!r} != fused {b!r} "
                           f"(atol {LOSS_ATOL})")
    log(f"first-step loss einsum {a!r} fused {b!r} |diff| {abs(a - b)!r}")
    return abs(a - b)


def lower_jsc(layers, params, in_f: int = IN_F, in_i: int = IN_I):
    """Trained LUT-Dense stack -> DAIS program (the verifier runs)."""
    from repro.core.dais import compile_sequential

    t0 = time.monotonic()
    prog = compile_sequential(layers, params, in_f, in_i)
    log(f"lower jsc: {prog.n_instrs()} instrs in "
        f"{time.monotonic() - t0:.2f}s")
    return prog


def lower_pid(ctx: int = PID_CTX, seed: int = 0, hidden: int = 8):
    """The CEPC PID hybrid at ``ctx`` samples, random weights from seed."""
    import jax

    from repro.core.lower import lower
    from repro.models.pid import (build_pid_graph, build_pid_layers,
                                  init_pid_params)

    t0 = time.monotonic()
    layers = build_pid_layers(hidden=hidden)
    params = init_pid_params(layers, jax.random.PRNGKey(seed))
    prog = lower(build_pid_graph(layers, n_samples=ctx), [*params, None])
    log(f"lower pid-hybrid ctx={ctx}: {prog.n_instrs()} instrs in "
        f"{time.monotonic() - t0:.2f}s")
    return prog


def build_phase(name: str, prog, *, engine=None, require=None, mesh=None,
                seed: int = 0):
    """``serve.api.build`` with the full bit-exact gate."""
    from repro.serve.api import EngineSpec, build

    t0 = time.monotonic()
    built = build(prog, EngineSpec(engine=engine, require=require,
                                   verify="full", mesh=mesh, seed=seed))
    e, att = built.engine, built.attestation
    log(f"{name}: path={e.path} dtype={np.dtype(e.dtype).name} "
        f"n_launches={e.n_launches} "
        f"packed_table_bytes={e.packed_table_bytes} "
        f"build+gate {time.monotonic() - t0:.2f}s (compile-inclusive; "
        f"gate {att['random']} random + {att['exhaustive']} exhaustive "
        f"rows bit-exact)")
    return built


def tier_phase(name: str, built, *, n_requests: int = N_REQUESTS,
               n_replicas: int = 2, seed: int = 0) -> int:
    """Single-row requests through a ServeTier; all must be bit-exact."""
    from repro.kernels.lut_serve import input_code_bounds
    from repro.serve.api import tier_from_built
    from repro.serve.tier import TierConfig

    prog = built.prog
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(seed).integers(
        lo, hi + 1, (n_requests, len(lo)), dtype=np.int64)
    ref = np.asarray(prog.run(codes), np.int64)
    t0 = time.monotonic()
    tier = tier_from_built({name: built}, TierConfig(n_replicas=n_replicas))
    warm_s = time.monotonic() - t0
    try:
        t0 = time.monotonic()
        futures = [tier.submit(row, name) for row in codes]
        out = np.stack([np.asarray(f.result(timeout=600), np.int64)
                        for f in futures])
        serve_s = time.monotonic() - t0
    finally:
        tier.stop()
    n_exact = int(np.all(out == ref, axis=1).sum())
    log(f"{name} tier: {n_replicas} replicas, warm-up (compile-inclusive) "
        f"{warm_s:.2f}s, {n_requests} requests in {serve_s:.3f}s, "
        f"{n_exact}/{n_requests} bit-exact vs DaisProgram.run")
    if n_exact != n_requests:
        raise RuntimeError(f"{name}: {n_requests - n_exact} responses "
                           f"differ from DaisProgram.run")
    return n_exact


def mesh_phase(name: str, prog, mesh, *, engine=None, require=None,
               batch: int = N_REQUESTS, seed: int = 0):
    """One batch through an engine built on ``mesh``: its output must be
    sharded across every device of the mesh and bit-exact."""
    from repro.kernels.lut_serve import input_code_bounds

    built = build_phase(name, prog, engine=engine, require=require,
                        mesh=mesh, seed=seed)
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(seed + 1).integers(
        lo, hi + 1, (batch, len(lo)), dtype=np.int64)
    out = built.engine.run(codes)
    n_dev = len(out.sharding.device_set)
    exact = bool(np.array_equal(np.asarray(out, np.int64), prog.run(codes)))
    log(f"{name}: batch {batch} output sharding {out.sharding.spec} over "
        f"{n_dev} devices, bit-exact {exact}")
    if n_dev != mesh.devices.size or not exact:
        raise RuntimeError(f"{name}: output over {n_dev} of "
                           f"{mesh.devices.size} devices, bit-exact {exact}")
    return built


# --------------------------------------------------------------------------- #
# entry point
# --------------------------------------------------------------------------- #
def run_one_chip(seed: int) -> None:
    einsum = train_phase(fused=False, seed=seed)
    fused = train_phase(fused=True, seed=seed)
    compare_losses(einsum, fused)
    programs = {"jsc": lower_jsc(einsum["layers"], einsum["params"]),
                "pid": lower_pid(seed=seed)}
    for model, prog in programs.items():
        for engine, require in ((None, "fused"), ("pallas", "pallas")):
            name = f"{model}-{require}"
            built = build_phase(name, prog, engine=engine, require=require,
                                seed=seed)
            tier_phase(name, built, seed=seed)


def run_four_chips(seed: int) -> None:
    import jax

    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh()
    layers = jsc_layers()
    keys = jax.random.split(jax.random.PRNGKey(seed), len(layers))
    programs = {"jsc": lower_jsc(layers, [l.init(k)
                                          for l, k in zip(layers, keys)]),
                "pid": lower_pid(seed=seed)}
    for model, prog in programs.items():
        for engine, require in ((None, "fused"), ("pallas", "pallas")):
            name = f"{model}-{require}-mesh{mesh.devices.size}"
            built = mesh_phase(name, prog, mesh, engine=engine,
                               require=require, seed=seed)
            tier_phase(name, built, n_replicas=4, seed=seed)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: every phase on one chip; 4: only the "
                         "mesh-sharded serve path over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {devices[0].platform!r} "
              f"devices", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    log(f"device_kind={devices[0].device_kind!r} device_count="
        f"{len(devices)} compile cache {enable_compile_cache()}")
    t0 = time.monotonic()
    (run_four_chips if args.chips == 4 else run_one_chip)(args.seed)
    log(f"all phases passed in {time.monotonic() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
