"""Kernel micro-bench: einsum chain vs fused Pallas LUT-Dense, fwd + bwd.

Writes ``BENCH_kernels.json`` with, per shape: forward and backward (full
train-mode VJP over all 9 inputs) median walltime for both implementations,
plus an analytic peak-HBM-intermediate estimate.  A ``serve_kernels``
section compares the serve-side lowerings at the acceptance batch: the
fused per-stage engine vs the single-launch bit-packed Pallas mega-kernel
(``kernels/lut_serve_pallas.py``) — walltime, launches per inference
(n_stages vs 1), and packed-table bytes vs the fused int64 tables.  The structural point of the
fused pair is the memory column: the einsum train path materialises the
(B, C_in, H, C_out) hidden tensor in HBM twice (forward save + cotangent
rebuild), while the fused forward and the recompute backward keep every
per-``j`` intermediate in a (TB, H, TCO) VMEM tile.

On a CPU backend the fused kernels run in Pallas *interpret* mode
(per-grid-instance Python), so walltime favours XLA's compiled einsum — the
``interpret_mode`` flag is recorded so downstream trajectory tooling doesn't
read CPU walltime as the TPU story.

``smoke=True`` (CI: ``python -m benchmarks.run --only kernels --smoke``)
runs one tiny shape with single-iteration timing and skips the JSON write —
it proves the benchmark still runs without publishing CI-container numbers.

Run:  PYTHONPATH=src python -m benchmarks.run --only kernels
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_call
from repro.kernels import ops
from repro.kernels.lut_dense import DEF_TB, DEF_TCO
from repro.kernels.ref import lut_dense_train_ref

# (B, C_in, H, C_out) — small enough for interpret mode, big enough that the
# einsum hidden tensor dominates its peak memory
SHAPES = [(256, 16, 8, 20), (512, 16, 8, 32), (1024, 32, 8, 64)]
OUT_JSON = "BENCH_kernels.json"


def _inputs(b, ci, h, co, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    x = (jax.random.normal(ks[0], (b, ci)) * 3).astype(jnp.float32)
    w0 = jax.random.normal(ks[1], (ci, h, co))
    b0 = jax.random.normal(ks[2], (ci, h, co)) * 0.5
    wo = jax.random.normal(ks[3], (ci, h, co)) * 0.3
    bo = jax.random.normal(ks[4], (ci, co)) * 0.1
    fi = jax.random.randint(ks[5], (ci, co), 0, 7).astype(jnp.float32)
    ii = jnp.full((ci, co), 3.0)
    fo = jax.random.randint(ks[6], (ci, co), 0, 7).astype(jnp.float32)
    io = jnp.full((ci, co), 3.0)
    cot = jax.random.normal(ks[7], (b, co))
    return (x, w0, b0, wo, bo, fi, ii, fo, io), cot


def _peak_bytes(b, ci, h, co):
    """fp32 bytes of the largest *intermediate* each path materialises in HBM
    (weights/inputs/outputs are common to both and excluded)."""
    tb, tco = min(DEF_TB, b), min(DEF_TCO, co)
    einsum = (b * ci * h * co      # hidden tanh activations, saved for bwd
              + b * ci * co * 2)   # broadcast xq + pre-quant y
    fused = (tb * h * tco          # per-j hidden tile, VMEM-resident
             + tb * tco * 2        # xq / y tiles
             + (co + tco - 1) // tco * b * ci)  # bwd dx partials (HBM)
    return {"einsum": einsum * 4, "fused": fused * 4}


SMOKE_SHAPES = [(32, 4, 4, 8)]

# serve-kernel section: LUT-Dense stacks compiled to DAIS and served at the
# acceptance batch, per-stage fused engine vs the single-launch mega-kernel
SERVE_MODELS = [([16, 20, 5], 8), ([32, 32, 5], 8)]
SERVE_BATCH = 1024


def _serve_kernel_rows(smoke: bool) -> list:
    """Per-stage vs mega-kernel serve microbench (ISSUE 6).

    Columns per model: walltime of the fused per-stage engine (one XLA op
    chain per stage) vs the single-``pallas_call`` mega-kernel, launches
    per inference, and the packed-table footprint (lane-packed,
    out-shift-folded) vs the int64 tables the fused engine gathers from.
    Both engines pass ``verify_engine`` before anything is timed.
    """
    import numpy as np

    from benchmarks.serve_bench import IN_F, IN_I, _build
    from repro.core.quant import quantize_to_int
    from repro.kernels.lut_serve import compose_fused_stages
    from repro.serve.api import EngineSpec, build

    models = SERVE_MODELS[:1] if smoke else SERVE_MODELS
    batch = 128 if smoke else SERVE_BATCH
    warmup, iters = (1, 1) if smoke else (2, 15)
    rng = np.random.default_rng(0)
    rows = []
    for dims, hidden in models:
        prog = _build(dims, hidden)
        codes = quantize_to_int(rng.normal(0.0, 2.0, (batch, dims[0])),
                                IN_F, IN_I, True, "SAT")
        engines = {}
        for name in ("fused", "pallas"):
            # require=name: a path downgrade fails the bench, and the
            # spec's default verify policy gates before anything is timed
            engines[name] = build(prog, EngineSpec(
                engine=name, require=name, n_random=256)).engine
        stages, _ = compose_fused_stages(prog)
        fused_table_bytes = int(sum(
            np.asarray(st.table, np.int64).nbytes
            for st in stages.stages if st.kind == "lut"))
        xs = {n: jnp.asarray(codes, e.dtype) for n, e in engines.items()}
        us = {n: time_call(e._runner, xs[n], warmup=warmup, iters=iters)
              for n, e in engines.items()}
        shape = "x".join(map(str, dims))
        row = {
            "dims": dims, "hidden": hidden, "batch": batch,
            "per_stage_us": us["fused"], "mega_kernel_us": us["pallas"],
            "speedup_mega_vs_per_stage": us["fused"] / us["pallas"],
            "launches_per_inference": {
                "fused": engines["fused"].n_launches,
                "pallas": engines["pallas"].n_launches},
            "packed_table_bytes": engines["pallas"].packed_table_bytes,
            "fused_table_bytes": fused_table_bytes,
        }
        rows.append(row)
        emit(f"kernels/serve/mega/{shape}", us["pallas"],
             f"vs_per_stage={us['fused'] / us['pallas']:.2f}x;"
             f"launches={engines['fused'].n_launches}->1;"
             f"packed_B={row['packed_table_bytes']}"
             f"/{fused_table_bytes}")
        emit(f"kernels/serve/per_stage/{shape}", us["fused"],
             f"launches={engines['fused'].n_launches}")
    return rows


def run(smoke: bool = False) -> None:
    interpret = jax.default_backend() != "tpu"
    shapes = SMOKE_SHAPES if smoke else SHAPES
    warmup, iters = (1, 1) if smoke else (1, 3)
    results = []
    for b, ci, h, co in shapes:
        args, cot = _inputs(b, ci, h, co)
        argnums = tuple(range(9))

        fwd_e = jax.jit(lut_dense_train_ref)
        fwd_f = jax.jit(ops.lut_dense)
        bwd_e = jax.jit(jax.grad(
            lambda *a: jnp.sum(lut_dense_train_ref(*a) * cot), argnums=argnums))
        bwd_f = jax.jit(jax.grad(
            lambda *a: jnp.sum(ops.lut_dense(*a) * cot), argnums=argnums))

        row = {
            "b": b, "c_in": ci, "h": h, "c_out": co,
            "fwd_us": {
                "einsum": time_call(fwd_e, *args, warmup=warmup, iters=iters),
                "fused": time_call(fwd_f, *args, warmup=warmup, iters=iters)},
            "bwd_us": {
                "einsum": time_call(bwd_e, *args, warmup=warmup, iters=iters),
                "fused": time_call(bwd_f, *args, warmup=warmup, iters=iters)},
            "peak_intermediate_bytes": _peak_bytes(b, ci, h, co),
        }
        results.append(row)
        shape = f"{b}x{ci}x{h}x{co}"
        for d in ("fwd", "bwd"):
            for impl in ("einsum", "fused"):
                emit(f"kernels/{d}/{impl}/{shape}", row[f"{d}_us"][impl],
                     f"peak_B={row['peak_intermediate_bytes'][impl]}")

    serve_rows = _serve_kernel_rows(smoke)
    if smoke:
        assert serve_rows and all(
            r["launches_per_inference"]["pallas"] == 1
            and r["packed_table_bytes"] > 0 for r in serve_rows)
        emit("kernels/smoke_ok", 0.0, "json_not_written")
        return
    payload = {
        "backend": jax.default_backend(),
        "interpret_mode": interpret,
        "tile": {"tb": DEF_TB, "tco": DEF_TCO},
        "note": ("fused fwd+bwd never materialise the (B,C_in,H,C_out) hidden "
                 "tensor; interpret-mode walltime on CPU is not the TPU story"),
        "results": results,
        "serve_kernels": {
            "batch": SERVE_BATCH,
            "note": ("per_stage = kernels/lut_serve.py fused engine (one "
                     "jitted op chain per stage); mega_kernel = kernels/"
                     "lut_serve_pallas.py single pallas_call over the whole "
                     "chain, lane-packed out-shift-folded tables; both "
                     "bit-exact-gated before timing"),
            "results": serve_rows,
        },
    }
    with open(OUT_JSON, "w") as fh:
        json.dump(payload, fh, indent=2)
    emit("kernels/json_written", 0.0, OUT_JSON)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="one tiny shape, no JSON overwrite (CI)")
    run(smoke=ap.parse_args().smoke)
