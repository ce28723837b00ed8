"""Training launcher with checkpoint/restart fault tolerance.

Single-host CPU runs exercise the *same* code path the production mesh
would: the step function, shardings, checkpoint cadence, β schedule and
data-pipeline cursor all behave identically; only the mesh differs.

The hot loop is **scan-chunked** (``train/loop.py``): ``--chunk-steps`` K
optimizer steps run inside ONE jitted ``lax.scan`` call with a donated
``(params, opt_state)`` carry, metrics accumulate on device and cross to
the host once per chunk, and batch synthesis + host→device transfer for
the next chunk run on a background prefetch thread (``data/pipeline.py``;
``--no-prefetch`` for the synchronous fallback).  Chunk boundaries are
planned to land exactly on the checkpoint cadence and the simulated-crash
step, so fault-tolerance semantics are identical to the per-step loop —
and so is every bit of the result (BENCH_train.json asserts it).

Fault-tolerance model (designed for 1000+ nodes, demonstrated here):

* every K steps an **async atomic** checkpoint is written (params + Adam
  state + data cursor + RNG);  restart resumes bit-exactly from the last
  one — ``--simulate-crash N`` kills the process at step N to let tests
  prove it (tests/test_ckpt.py, tests/test_train_loop.py — including
  restarts from steps that are NOT chunk-aligned);
* the data pipeline is a pure function of (seed, step, host) — a replaced
  host needs no coordination to rejoin, and the prefetch thread changes
  *when* batches are built, never *which* (the determinism contract in
  ``data/pipeline.py``);
* a step-time watchdog (EMA) flags stragglers; on a real fleet this signal
  feeds the controller that evicts/replaces slow hosts — here it logs.
  Chunk walltime is measured at real boundaries (the once-per-chunk
  metrics transfer blocks on the device), and compile-inclusive chunks
  (the first occurrence of each chunk length) never seed or trip the EMA;
* elastic restarts: checkpoints are mesh-shape-agnostic (ckpt/store.py),
  so a job restarted on a different device count re-shards on restore.

Usage:
    PYTHONPATH=src python -m repro.launch.train --arch olmo_1b --steps 100 \
        --batch 8 --seq 128 --ckpt-dir /tmp/run1
"""

from __future__ import annotations

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    # β trade-off schedule.  None defaults matter: `or`-style fallbacks would
    # silently turn an explicit `--beta-final 0.0` into "constant β" and ramp
    # the default run from β=0 (log(0) → NaN loss).
    ap.add_argument("--beta-init", type=float, default=None,
                    help="β at step 0 (default: 0 constant, or 5e-7 — the "
                         "paper's ramp start — when --beta-final is set)")
    ap.add_argument("--beta-final", type=float, default=None,
                    help="β at the last step for the exponential ramp "
                         "(omit for constant β at --beta-init)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--chunk-steps", type=int, default=8,
                    help="optimizer steps per jitted lax.scan chunk; chunks "
                         "never cross --ckpt-every/--simulate-crash "
                         "boundaries (1 = per-step dispatch)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="build batch chunks synchronously on the critical "
                         "path instead of on the background prefetch thread")
    ap.add_argument("--simulate-crash", type=int, default=0,
                    help="exit(17) after this step (fault-tolerance tests)")
    ap.add_argument("--straggler-factor", type=float, default=2.0)
    args = ap.parse_args(argv)

    from repro.ckpt.store import CheckpointStore
    from repro.configs.base import get_config, get_smoke
    from repro.core.ebops import BetaSchedule, beta_ramp_error
    from repro.data.synthetic import lm_batch
    from repro.models.registry import build_model
    from repro.obs import gc_stats, watch_gc
    from repro.optim.adam import AdamConfig, cosine_restarts
    from repro.train.loop import chunked_train
    from repro.train.steps import TrainHParams, init_state, make_train_step

    if args.beta_final is None:
        beta_init = args.beta_init if args.beta_init is not None else 0.0
    else:
        # ramp requested: default the start to the paper's 5e-7 (§V-A)
        beta_init = args.beta_init if args.beta_init is not None else 5e-7
    err = beta_ramp_error(beta_init, args.beta_final)
    if err:
        raise SystemExit(f"--beta-init/--beta-final: {err}")
    if args.chunk_steps < 1:
        raise SystemExit(f"--chunk-steps {args.chunk_steps}: must be >= 1")
    watch_gc()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    hp = TrainHParams(
        adam=AdamConfig(lr=args.lr),
        beta=BetaSchedule(beta_init, args.beta_final, args.steps),
        lr_schedule=cosine_restarts(args.lr, first_period=max(args.steps // 2, 10),
                                    warmup=min(20, args.steps // 10 + 1)),
    )
    raw_step, _ = make_train_step(model, mesh=None, hp=hp, jit=False)

    key = jax.random.PRNGKey(args.seed)
    params, opt = init_state(model, key)
    start_step = 0
    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    if store and store.latest_step() is not None:
        params, opt, manifest = store.restore(params, opt)
        params = jax.tree.map(jnp.asarray, params)
        opt = jax.tree.map(jnp.asarray, opt)
        start_step = manifest["step"]
        print(f"[train] resumed from step {start_step}")

    # pure function of (seed, step) — runs on the prefetch thread, so the
    # modality-stub RNG and lm_batch synthesis leave the critical path
    stub_specs = {k: (tuple(v.shape), np.dtype(v.dtype))
                  for k, v in model.input_specs(args.seq, args.batch,
                                                "train").items()
                  if k not in ("tokens", "labels")}

    def get_batch(step: int) -> dict:
        out = dict(lm_batch(args.seed, step, args.batch, args.seq, cfg.vocab))
        for k, (shape, dtype) in stub_specs.items():
            # modality stubs: deterministic pseudo-embeddings
            rng = np.random.default_rng([args.seed, step, 7])
            out[k] = rng.normal(0, 1, shape).astype(dtype)
        return out

    # chunks must END on every step with host-visible side effects
    boundaries = set(range(args.ckpt_every, args.steps, args.ckpt_every))
    if args.simulate_crash:
        boundaries.add(max(args.simulate_crash, start_step + 1))

    def save(step: int, blocking: bool = False) -> None:
        store.save(step, params, opt,
                   extra={"seed": args.seed, "arch": args.arch},
                   blocking=blocking)

    ema = None
    metrics = None
    for res in chunked_train(raw_step, params, opt, get_batch,
                             start_step, args.steps,
                             chunk_steps=args.chunk_steps,
                             boundaries=boundaries,
                             prefetch=not args.no_prefetch):
        params, opt, metrics = res.params, res.opt_state, res.metrics
        for i in range(res.k):
            step = res.step + i
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"[train] step {step:5d} "
                      f"loss={metrics['loss'][i]:.4f} "
                      f"ce={metrics['ce'][i]:.4f} "
                      f"ebops={metrics['ebops'][i]:.3g} "
                      f"gnorm={metrics['grad_norm'][i]:.3f} "
                      f"lr={metrics['lr'][i]:.2e}", flush=True)
        # watchdog: dt_s is measured dispatch→host-visible (the metrics
        # transfer blocks on the whole chunk), and compile-inclusive chunks
        # are excluded so the first step never seeds the straggler EMA
        if not res.compiled:
            dt_step = res.dt_s / res.k
            if ema is not None and dt_step > args.straggler_factor * ema:
                print(f"[watchdog] steps {res.step}..{res.step + res.k - 1} "
                      f"took {dt_step:.3f}s/step (EMA {ema:.3f}s) — "
                      f"straggler signal", flush=True)
            ema = dt_step if ema is None else 0.9 * ema + 0.1 * dt_step
        end = res.step + res.k
        if store and end % args.ckpt_every == 0:
            save(end)
        if args.simulate_crash and end >= args.simulate_crash:
            if store:
                save(end, blocking=True)
            print(f"[train] simulating crash at step {end}", flush=True)
            os._exit(17)

    if store:
        save(args.steps, blocking=True)
    final = float(metrics["loss"][-1])
    print(f"[train] done: {args.steps} steps, final loss {final:.4f}, "
          f"collector pauses {gc_stats().pause_s * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
