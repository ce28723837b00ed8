"""β trade-off Pareto sweep: ONE training run → a served operating point.

The paper's headline methodological claim (§III-B, §V-A) is that a single
β-ramped training run with element-wise zero-bit pruning replaces manual
bit-width tuning: snapshots taken along the exponential β ramp trace the
accuracy↔resource frontier without per-point retraining.  This launcher is
that claim as one command, end to end through the *hardware* pipeline:

1. **train once** — the quickstart JSC-HLF LUT-Dense stack under the
   CE + β(step)·EBOPs objective (``train/steps.make_lut_train_step``),
   with β ramping ``--beta-init`` → ``--beta-final`` (defaults: the
   paper's 5e-7 → 1e-3) and snapshots checkpointed along the ramp via
   ``ckpt/store``;
2. **compile every snapshot** — restore, measure accuracy, extract truth
   tables, lower to DAIS, run the dead-cell elimination pass
   (``core/opt.py``), build the fused accelerator engine, and gate it
   bit-exactly against the *unoptimized* interpreter (``verify_engine``);
3. **report the frontier** — per snapshot: accuracy, EBOPs, estimated
   FPGA LUTs, live-LUT count (post-DCE LLUT instructions), fused gather
   width before/after DCE, and measured engine latency — printed as a
   table and written to ``--out`` (``BENCH_pareto.json``);
4. **select + serve** — pick the cheapest frontier point within
   ``--select-tol`` of the best validation accuracy, persist it as a
   compiled-artifact bundle whose attestation records the snapshot's
   β / EBOPs / gate statistics (``serve/artifact.py``), cold-start an
   engine from the bundle, and serve real requests through the async
   micro-batching scheduler (``serve/scheduler.py``).

Usage:
    PYTHONPATH=src python -m repro.launch.pareto                # full sweep
    PYTHONPATH=src python -m repro.launch.pareto --smoke        # seconds
    PYTHONPATH=src python -m repro.launch.pareto --steps 2000 \
        --beta-final 3e-4 --snapshots 10 --out BENCH_pareto.json
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

IN_F, IN_I = 4, 3     # quickstart/JSC input grid


def build_argparser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale CI run: few steps, small data, "
                         "same train -> snapshot -> compile -> serve path")
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--snapshots", type=int, default=None,
                    help="checkpoints taken along the ramp (>= 3)")
    ap.add_argument("--beta-init", type=float, default=5e-7)
    ap.add_argument("--beta-final", type=float, default=1e-3,
                    help="paper §V-A HLF JSC ramp endpoint")
    ap.add_argument("--dims", default="16,20,5",
                    help="LUT-Dense stack widths (in,...,out)")
    ap.add_argument("--hidden", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="snapshot directory (default: a fresh temp dir)")
    ap.add_argument("--chunk-steps", type=int, default=8,
                    help="optimizer steps per jitted lax.scan chunk in the "
                         "β-ramped training run (train/loop.py); chunks "
                         "never cross snapshot boundaries")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="synthesize training batches synchronously instead "
                         "of on the background prefetch thread")
    ap.add_argument("--out", default="BENCH_pareto.json",
                    help="frontier JSON output path (note: the default "
                         "overwrites the committed BENCH_pareto.json, whose "
                         "published numbers come from benchmarks/"
                         "pareto_bench.py's pinned configuration)")
    ap.add_argument("--select-tol", type=float, default=0.02,
                    help="serve the cheapest point within this much "
                         "validation accuracy of the best snapshot")
    ap.add_argument("--serve-requests", type=int, default=None,
                    help="requests pushed through the scheduler for the "
                         "selected operating point (0 disables serving)")
    ap.add_argument("--engine", choices=("fused", "pallas"), default="fused",
                    help="serving engine for the per-snapshot latency "
                         "columns and the served operating point: fused "
                         "per-stage JAX ops (default) or the single-launch "
                         "bit-packed Pallas mega-kernel")
    ap.add_argument("--verify-rtl", action="store_true",
                    help="before bundling the selected operating point, "
                         "emit its (DCE'd) Verilog and assert the three-way "
                         "attestation RTL sim == unoptimized interpreter == "
                         "engine (core/rtl.verify_rtl); the bundle's "
                         "attestation gains an 'rtl' entry with the Verilog "
                         "SHA-256 and verdict")
    return ap


def _quantize(x):
    from repro.core.quant import int_to_float, quantize_to_int
    return int_to_float(quantize_to_int(x, IN_F, IN_I, True, "SAT"), IN_F)


def _snapshot_steps(steps: int, n: int):
    """n distinct checkpoint steps, evenly spaced, ending at ``steps``."""
    raw = [max(1, round(steps * (k + 1) / n)) for k in range(n)]
    return sorted(set(raw))


def _bench_engine(engine, prog, batch: int, rounds: int, seed: int) -> dict:
    """Median-free best-of-N engine walltime on random in-range codes."""
    from repro.kernels.lut_serve import input_code_bounds

    lo, hi = input_code_bounds(prog)
    rng = np.random.default_rng(seed)
    codes = jnp.asarray(
        rng.integers(lo, hi + 1, (batch, len(lo)), np.int64), engine.dtype)
    jax.block_until_ready(engine._runner(codes))        # compile + warm
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        jax.block_until_ready(engine._runner(codes))
        best = min(best, time.perf_counter() - t0)
    return {"engine_us": best * 1e6, "rows_per_s": batch / best}


def run(args) -> dict:
    """Execute the sweep; returns (and writes) the frontier payload."""
    from repro.ckpt.store import CheckpointStore
    from repro.core.dais import compile_sequential
    from repro.core.ebops import BetaSchedule, ebops_lut_np, estimate_luts
    from repro.core.lut_layers import LUTDense
    from repro.core.opt import eliminate_dead_cells
    from repro.core.tables import extract_tables
    from repro.data.synthetic import jsc_hlf
    from repro.kernels.lut_serve import compile_program, verify_engine
    from repro.optim.adam import AdamConfig, cosine_restarts
    from repro.train.loop import chunked_train
    from repro.train.steps import TrainHParams, make_lut_train_step

    # None defaults + explicit validation — no falsy-`or` fallbacks (the
    # bug class the train.py β flags had: an explicit 0 must error, not
    # silently become the default)
    steps = args.steps if args.steps is not None else (60 if args.smoke
                                                      else 1500)
    batch = args.batch if args.batch is not None else (256 if args.smoke
                                                      else 1024)
    n_snap = args.snapshots if args.snapshots is not None else \
        (3 if args.smoke else 8)
    if steps <= 0 or batch <= 0:
        raise SystemExit(f"--steps {steps} / --batch {batch}: both must "
                         f"be positive")
    if args.chunk_steps < 1:
        raise SystemExit(f"--chunk-steps {args.chunk_steps}: must be >= 1")
    # same CLI contract as launch/train.py: a non-positive ramp endpoint or
    # start is a clean error here, not a traceback (or a swallowed warning)
    from repro.core.ebops import beta_ramp_error
    err = beta_ramp_error(args.beta_init, args.beta_final)
    if err:
        raise SystemExit(f"--beta-init/--beta-final: {err}")
    if n_snap < 3:
        raise SystemExit(f"--snapshots {n_snap}: the frontier needs at "
                         f"least 3 operating points")
    if steps < n_snap:
        raise SystemExit(
            f"--steps {steps} cannot fit {n_snap} distinct snapshots; "
            f"raise --steps or lower --snapshots")
    n_train, n_eval = (2000, 500) if args.smoke else (20000, 5000)
    bench_batch = 128 if args.smoke else 1024
    bench_rounds = 3 if args.smoke else 15
    n_requests = args.serve_requests
    if n_requests is None:
        n_requests = 96 if args.smoke else 1024

    dims = [int(d) for d in args.dims.split(",")]
    if len(dims) < 2:
        raise SystemExit("--dims needs at least in,out (e.g. 16,5)")

    # ------------------------------------------------------------- data
    xtr, ytr = jsc_hlf(args.seed, n_train, "train")
    xval, yval = jsc_hlf(args.seed, n_eval, "val")
    xte, yte = jsc_hlf(args.seed, n_eval, "test")
    xtr, xval, xte = _quantize(xtr), _quantize(xval), _quantize(xte)

    # ------------------------------------------------------------ model
    layers = [LUTDense(ci, co, hidden=args.hidden, use_batchnorm=(k == 0))
              for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:]))]
    beta = BetaSchedule(args.beta_init, args.beta_final, steps)
    hp = TrainHParams(
        adam=AdamConfig(lr=args.lr),
        beta=beta,
        lr_schedule=cosine_restarts(args.lr, first_period=max(steps // 3, 10),
                                    warmup=min(30, steps // 10 + 1)))
    # raw (un-jitted) step: the chunked driver scans K of them per launch
    raw_step, init_fn = make_lut_train_step(layers, hp, jit=False)
    params, opt = init_fn(jax.random.PRNGKey(args.seed))
    ref_params = jax.tree.map(np.asarray, params)

    @jax.jit
    def evaluate(ps, x, y):
        h = x
        for idx, layer in enumerate(layers):
            h, _ = layer.apply(ps[f"l{idx}"], h, train=False)
        return jnp.mean(jnp.argmax(h, -1) == y)

    # ------------------------------------------- train once, snapshotting
    ckpt_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="pareto_ckpt_")
    store = CheckpointStore(ckpt_dir, keep=n_snap + 1)
    if store.list_steps():
        # CheckpointStore GC keeps the globally highest step numbers, so a
        # directory holding an earlier (longer) run would evict THIS run's
        # snapshots — or restore stale params under fresh β labels
        raise SystemExit(
            f"--ckpt-dir {ckpt_dir} already contains checkpoints "
            f"(steps {store.list_steps()}); use an empty directory per "
            f"sweep so snapshot retention and restore stay unambiguous")
    snap_steps = _snapshot_steps(steps, n_snap)
    print(f"[pareto] one β-ramped run: {steps} steps, "
          f"β {args.beta_init:.1e} -> {args.beta_final:.1e}, "
          f"snapshots at {snap_steps} (chunks of {args.chunk_steps}, "
          f"prefetch {'off' if args.no_prefetch else 'on'}) -> {ckpt_dir}")
    # stateful host RNG drawn once per step: the prefetch thread calls
    # get_batch strictly in step order, so the index stream is identical
    # to the old synchronous per-step loop (data/pipeline.py contract)
    rng = np.random.default_rng(args.seed)
    xtr_np, ytr_np = np.asarray(xtr), np.asarray(ytr)

    def get_batch(_step: int) -> dict:
        idx = rng.integers(0, len(xtr_np), batch)
        return {"x": xtr_np[idx], "y": ytr_np[idx]}

    snap_set = set(snap_steps)
    t0 = time.time()
    for res in chunked_train(raw_step, params, opt, get_batch, 0, steps,
                             chunk_steps=args.chunk_steps,
                             boundaries=snap_steps,
                             prefetch=not args.no_prefetch):
        params, opt = res.params, res.opt_state
        losses = res.metrics["loss"]
        if not np.all(np.isfinite(losses)):
            bad = res.step + int(np.argmin(np.isfinite(losses)))
            raise RuntimeError(f"non-finite loss at step {bad}: "
                               f"{losses[bad - res.step]} — β ramp broken?")
        end = res.step + res.k
        if end in snap_set:
            store.save(end, params, extra={"beta": float(beta(end - 1)),
                                           "step": end}, blocking=True)
            print(f"[pareto] step {end:5d}  β={float(beta(end - 1)):.2e}  "
                  f"loss={losses[-1]:.4f}  "
                  f"ebops={res.metrics['ebops'][-1]:.3g}", flush=True)
    t_train = time.time() - t0

    # ------------------------------- compile + measure every snapshot
    points = []
    # snap -> (opt_prog, gate, prog, engine) for _serve_selected; the
    # UNoptimized prog and the snapshot's engine ride along so the selected
    # point's --verify-rtl attestation can be three-way without re-lowering
    compiled = {}
    for snap in snap_steps:
        ps, _opt, manifest = store.restore(ref_params, step=snap)
        ps = jax.tree.map(jnp.asarray, ps)
        val_acc = float(evaluate(ps, jnp.asarray(xval), jnp.asarray(yval)))
        test_acc = float(evaluate(ps, jnp.asarray(xte), jnp.asarray(yte)))
        params_list = [ps[f"l{k}"] for k in range(len(layers))]

        tables = [extract_tables(layer, p)
                  for layer, p in zip(layers, params_list)]
        ebops = float(sum(ebops_lut_np(t.in_width, t.out_width)
                          for t in tables))
        prog = compile_sequential(layers, params_list, IN_F, IN_I)
        opt_prog, rep = eliminate_dead_cells(prog)
        engine = compile_program(opt_prog, engine=args.engine)
        gate = verify_engine(engine, prog,
                             n_random=256 if args.smoke else 1024,
                             seed=args.seed)
        bench = _bench_engine(engine, opt_prog, bench_batch, bench_rounds,
                              args.seed)
        compiled[snap] = (opt_prog, gate, prog, engine)
        gw0, gw1 = rep.total_gather_width()
        # static-analysis stats (core/analysis.py): proven vs required
        # widths and the live fraction of composed table entries, so
        # operating-point selection can prefer points that fit narrower
        # engines / smaller packed tables at equal accuracy
        from repro.core.analysis import analyze_ranges
        from repro.launch.lint import live_table_stats
        ranges = analyze_ranges(opt_prog)
        live = live_table_stats(opt_prog, ranges) or {}
        points.append({
            "step": snap, "beta": manifest["beta"],
            "val_acc": val_acc, "test_acc": test_acc,
            "ebops": ebops, "est_luts": estimate_luts(ebops),
            "n_llut": rep.n_llut_before, "n_llut_live": rep.n_llut_after,
            "gather_width": gw0, "gather_width_dce": gw1,
            "n_instrs": rep.n_instrs_before,
            "n_instrs_dce": rep.n_instrs_after,
            "engine_path": engine.path,
            "packed_table_bytes": engine.packed_table_bytes,
            "required_width": opt_prog.required_width(),
            "proven_width": ranges.proven_width(),
            "engine_width": ranges.engine_width(),
            **live,
            "bench_batch": bench_batch, **bench,
            "verify": gate,
        })
        live_pct = (100.0 * live["live_entries"] / live["table_entries"]
                    if live else float("nan"))
        print(f"[pareto] snap {snap:5d}  β={manifest['beta']:.2e}  "
              f"val={val_acc:.4f} test={test_acc:.4f}  "
              f"EBOPs={ebops:9.1f} est.LUTs={points[-1]['est_luts']:8.0f}  "
              f"LLUTs {rep.n_llut_before}->{rep.n_llut_after}  "
              f"gather {gw0}->{gw1}  "
              f"width req={points[-1]['required_width']} "
              f"proven={points[-1]['proven_width']}  "
              f"live={live_pct:.0f}%  "
              f"{bench['engine_us']:.0f} us/batch", flush=True)

    # ----------------------------------------------- frontier + selection
    by_cost = sorted(points, key=lambda p: (p["est_luts"], -p["val_acc"]))
    best_acc = -1.0
    for p in by_cost:
        p["on_frontier"] = p["val_acc"] > best_acc
        best_acc = max(best_acc, p["val_acc"])
    frontier = [p for p in by_cost if p["on_frontier"]]
    top = max(points, key=lambda p: p["val_acc"])
    selected = next(p for p in frontier
                    if p["val_acc"] >= top["val_acc"] - args.select_tol)
    print(f"[pareto] frontier: {len(frontier)}/{len(points)} points; "
          f"selected step {selected['step']} "
          f"(val {selected['val_acc']:.4f} vs best {top['val_acc']:.4f}, "
          f"est.LUTs {selected['est_luts']:.0f} vs {top['est_luts']:.0f})")

    # ------------------------------- serve the selected operating point
    serve_stats = None
    if n_requests > 0:
        opt_prog, gate, orig_prog, engine = compiled[selected["step"]]
        if args.verify_rtl:
            # hardware-level gate on the point we actually ship: the DCE'd
            # program's Verilog, simulated, vs the UNoptimized interpreter
            # vs the snapshot's engine; rides into the bundle attestation
            from repro.core.rtl import verify_rtl
            t0 = time.time()
            rtl = verify_rtl(opt_prog, oracle=orig_prog, engine=engine,
                             n_random=256 if args.smoke else 1024,
                             seed=args.seed)
            gate = {**gate, "rtl": rtl}
            print(f"[pareto] rtl gate PASSED for step {selected['step']}: "
                  f"{rtl['verdict']} over {rtl['random']} random + "
                  f"{rtl['exhaustive']} exhaustive rows (verilog sha256 "
                  f"{rtl['verilog_sha256'][:12]}, {time.time() - t0:.2f}s)")
        serve_stats = _serve_selected(args, store.dir, selected, opt_prog,
                                      gate, n_requests)

    # a default (temp) snapshot dir is working space, not a product: drop
    # it so repeated runs don't accumulate npz piles in /tmp.  An explicit
    # --ckpt-dir keeps snapshots AND the served bundle.
    keep_ckpts = args.ckpt_dir is not None
    if serve_stats is not None:
        serve_stats["bundle_kept"] = keep_ckpts
    if not keep_ckpts:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        print(f"[pareto] temp snapshot dir removed ({ckpt_dir}); pass "
              f"--ckpt-dir to keep snapshots + the served bundle")

    payload = {
        "task": "jsc_hlf",
        "dims": dims, "hidden": args.hidden,
        "steps": steps, "batch": batch, "train_wall_s": t_train,
        "beta_init": args.beta_init, "beta_final": args.beta_final,
        "selected_step": selected["step"],
        "select_tol": args.select_tol,
        "serve": serve_stats,
        "points": points,
        "note": ("single β-ramped training run; every point is one ckpt/store "
                 "snapshot pushed through extract_tables -> lower -> "
                 "core/opt DCE -> fused engine, gated bit-exact against the "
                 "unoptimized DaisProgram.run; est_luts is the paper's "
                 "exp(0.985·log EBOPs) calibration"),
    }
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"[pareto] wrote {args.out} ({len(points)} operating points)")
    return payload


def _serve_selected(args, bundle_dir, selected, opt_prog, gate,
                    n_requests: int) -> dict:
    """Bundle the chosen snapshot and serve it through the tier.

    ``opt_prog``/``gate`` are the DCE'd program and its verify statistics
    the per-snapshot loop already produced — nothing is re-lowered or
    re-gated here.  The bundle is registered into a
    :class:`~repro.serve.tier.ServeTier` (the same multi-replica registry
    path production serving uses, via ``repro.serve.api``) rather than a
    private one-off batcher; the interpreter comparison runs the identical
    open-loop driver against ``InterpreterBackend`` behind a MicroBatcher
    so the reported ratio stays service-path vs service-path.
    """
    from repro.kernels.lut_serve import input_code_bounds
    from repro.serve.api import EngineSpec, build, tier_from_built
    from repro.serve.artifact import save_artifact
    from repro.serve.scheduler import (InterpreterBackend, MicroBatcher,
                                       ServeConfig, drive_open_loop)
    from repro.serve.tier import TierConfig

    bundle = os.path.join(bundle_dir, f"pareto_step{selected['step']}.npz")
    # the attestation records WHICH operating point this bundle is: the
    # snapshot's β and EBOPs ride with the gate statistics under the
    # bundle's content hash (docs/serving.md)
    digest = save_artifact(bundle, opt_prog, attestation={
        **gate, "beta": selected["beta"], "ebops": selected["ebops"],
        "est_luts": selected["est_luts"], "step": selected["step"],
        "dce_llut": selected["n_llut_live"]})
    # verify="cached": the bundle's stored attestation is the per-snapshot
    # gate that just ran, tied to these bytes by the content hash
    built = build(bundle, EngineSpec(
        engine=None if args.engine == "fused" else args.engine,
        verify="cached"))
    print(f"[pareto] operating point bundled: {bundle} (hash {digest[:12]}, "
          f"attested β={built.attestation['beta']:.2e} "
          f"EBOPs={built.attestation['ebops']:.1f})")

    lo, hi = input_code_bounds(opt_prog)
    rng = np.random.default_rng(args.seed)
    codes = rng.integers(lo, hi + 1, (n_requests, len(lo)), np.int64)
    ref = np.asarray(opt_prog.run(codes), np.int64)
    name = f"pareto_step{selected['step']}"
    scfg = ServeConfig(max_batch=16 if args.smoke else 64, max_delay_ms=2.0)
    tier = tier_from_built({name: built},
                           TierConfig(n_replicas=2, serve=scfg),
                           start=False)
    with tier:
        out, drive = drive_open_loop(
            None, codes, rate=0.0,
            submit=lambda row: tier.submit(row, name))
    if not np.array_equal(out.astype(np.int64), ref):
        raise AssertionError("tier responses diverged from DaisProgram.run "
                             "— refusing to report serve numbers")
    s = tier.stats()
    with MicroBatcher(InterpreterBackend(opt_prog), scfg) as mb:
        _, idrive = drive_open_loop(mb, codes, rate=0.0)
    rows_per_s = n_requests / drive["wall_s"]
    interp_rows_per_s = n_requests / idrive["wall_s"]
    print(f"[pareto] served {n_requests} requests through the tier "
          f"({tier.config.n_replicas} replicas, model {name!r}): "
          f"p50={s.p50_ms:.2f} ms p99={s.p99_ms:.2f} ms "
          f"{rows_per_s:,.0f} rows/s "
          f"({rows_per_s / interp_rows_per_s:.1f}x the "
          f"interpreter behind the single-engine scheduler)")
    return {"bundle": bundle, "content_hash": digest,
            "n_requests": n_requests,
            "engine": {"p50_ms": s.p50_ms, "p99_ms": s.p99_ms,
                       "rows_per_s": rows_per_s},
            "tier": {"n_replicas": tier.config.n_replicas,
                     "n_batches": s.n_batches, "n_stolen": s.n_stolen},
            "interp_rows_per_s": interp_rows_per_s}


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    args = build_argparser().parse_args(argv)
    enable_compile_cache()
    run(args)


if __name__ == "__main__":
    main()
