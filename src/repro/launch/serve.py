"""Serving launcher: batched request loops for both engine families.

``--engine float`` (default) serves an LM arch config: batched prefill +
autoregressive greedy decode against the pre-allocated KV cache (the
production-mesh variant of the same step functions is exercised by
launch/dryrun.py).

``--engine tables`` serves the *compiled hardware artifact* of a LUT-Dense
stack: the model is lowered to a DAIS integer program
(``core.dais.compile_sequential``) and then to the accelerator-resident
engine (``kernels.lut_serve.compile_program``), with the request batch axis
sharded over the local mesh.  Before serving a single batch, a bit-exactness
gate asserts the jitted engine matches the numpy DAIS interpreter on random
and exhaustive-small inputs — we only serve what we verified.

``--engine pallas`` is ``--engine tables`` with the single-launch
bit-packed mega-kernel (``kernels.lut_serve_pallas``) preferred; a chain
that cannot pack degrades to the fused path with a compile-time
``EnginePathWarning``, and ``--require-pallas`` / ``--require-fused``
turn any such downgrade into a hard exit instead of a quiet perf loss.

``--verify-rtl`` extends the gate to the hardware level: the program's
emitted Verilog is evaluated by the RTL simulator (``core.rtl_sim``) and
asserted bit-exact against both the interpreter and the engine — a
three-way attestation recorded (Verilog SHA-256 + verdict) in the saved
bundle's metadata.

``--artifact <path>`` persists / reuses the compiled bundle
(``repro.serve.artifact``): when the file exists the launcher cold-starts
from it — no table extraction, no DAIS lowering, no fused-table composition
— and ``--skip-verify-cached`` additionally trusts the bundle's stored
attestation (protected by its content hash) instead of re-running the gate.

``--serve-loop`` switches from one pre-formed batch to the always-on
serving posture: an async micro-batching scheduler
(``repro.serve.scheduler``) coalesces individually submitted requests into
padded power-of-two batches, and a synthetic open-loop traffic driver
(Poisson arrivals at ``--rate`` req/s) reports p50/p99 latency and
throughput against the numpy-interpreter baseline.

``--model pid-hybrid`` swaps the LUT-Dense stack for the paper's hybrid
conv PID architecture (``repro.models.pid``), lowered through the graph
frontend (``core.lower``) so its conv layers share one table set across
all spatial sites and the engine runs on the fused shared-table path.
``--model jsc-plf`` serves the particle-list jet tagger
(``repro.models.plf``) the same way: its per-particle encoder shares one
table set across the 32 particle sites, then a set sum and a LUT head
(``stages=lut,sum,lut``).

Usage:
    PYTHONPATH=src python -m repro.launch.serve --arch qwen15_05b --smoke \
        --batch 4 --prompt-len 32 --gen 16
    PYTHONPATH=src python -m repro.launch.serve --engine tables \
        --lut-dims 16,20,5 --batch 1024 --gen 8
    PYTHONPATH=src python -m repro.launch.serve --engine tables \
        --model pid-hybrid --ctx 100 --batch 1024
    PYTHONPATH=src python -m repro.launch.serve --engine tables \
        --model jsc-plf --batch 1024
    PYTHONPATH=src python -m repro.launch.serve --engine tables \
        --artifact /tmp/model.npz --skip-verify-cached --serve-loop \
        --rate 2000 --requests 2048
"""

from __future__ import annotations

import argparse
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

# the model specs --engine tables (and launch/lint.py) can build
MODELS = ("lut-stack", "pid-hybrid", "jsc-plf")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="LM arch config (required for --engine float)")
    ap.add_argument("--engine", choices=("float", "tables", "pallas"),
                    default="float",
                    help="float: LM prefill/decode; tables: compiled "
                         "integer LUT artifact; pallas: tables with the "
                         "single-launch bit-packed mega-kernel preferred "
                         "(kernels/lut_serve_pallas.py)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    # --engine tables model spec (untrained init is fine: serving exactness
    # is a property of the compiled tables, not of the weights' quality)
    ap.add_argument("--model", choices=MODELS,
                    default="lut-stack",
                    help="lut-stack: LUT-Dense chain from --lut-dims; "
                         "pid-hybrid: the paper's hybrid conv PID model "
                         "(HGQ conv -> LUT convs -> LUT head -> window sum) "
                         "compiled through the graph frontend; jsc-plf: the "
                         "paper's particle-list jet tagger (per-particle "
                         "LUT encoder -> set sum -> LUT head)")
    ap.add_argument("--ctx", type=int, default=100,
                    help="pid-hybrid waveform context length in samples "
                         "(multiple of the 20-sample DAQ window)")
    ap.add_argument("--lut-dims", default="16,20,5",
                    help="comma-separated layer widths of the LUT-Dense stack")
    ap.add_argument("--lut-hidden", type=int, default=8)
    ap.add_argument("--in-f", type=int, default=4,
                    help="fractional bits of the request input grid")
    ap.add_argument("--in-i", type=int, default=2,
                    help="integer bits of the request input grid")
    # compiled-artifact cache + async serving loop (--engine tables only)
    ap.add_argument("--dce", action="store_true",
                    help="run the dead-cell elimination pass (core/opt.py) "
                         "on the lowered program before compiling; the "
                         "bit-exact gate then checks the optimized engine "
                         "against the UNoptimized interpreter")
    ap.add_argument("--lint", action="store_true",
                    help="print the static-analysis report (structural "
                         "verifier, per-register value ranges, proven vs "
                         "required widths — repro.launch.lint) for the "
                         "program before serving it")
    ap.add_argument("--artifact", default=None,
                    help="bundle path: load it when present, else compile "
                         "and save it there")
    ap.add_argument("--skip-verify-cached", action="store_true",
                    help="trust a loaded bundle's stored attestation "
                         "(content-hash protected) instead of re-running "
                         "the bit-exactness gate")
    ap.add_argument("--verify-rtl", action="store_true",
                    help="close the hardware loop: emit the program's "
                         "Verilog, run it through the RTL simulator "
                         "(core/rtl_sim.py), and assert the three-way "
                         "attestation RTL == interpreter == engine; the "
                         "saved bundle's attestation gains an 'rtl' entry "
                         "(Verilog SHA-256 + verdict)")
    ap.add_argument("--serve-loop", action="store_true",
                    help="async micro-batching scheduler + open-loop "
                         "synthetic traffic driver (p50/p99 + throughput); "
                         "with --replicas/--models it drives the "
                         "multi-replica tier instead of one MicroBatcher")
    ap.add_argument("--rate", type=float, default=2000.0,
                    help="offered load of the traffic driver, requests/s")
    ap.add_argument("--requests", type=int, default=1024,
                    help="total requests the traffic driver submits")
    ap.add_argument("--max-batch", type=int, default=64,
                    help="largest scheduler bucket (power of two)")
    ap.add_argument("--max-delay-ms", type=float, default=2.0,
                    help="scheduler coalescing deadline per request")
    ap.add_argument("--workers", type=int, default=1,
                    help="scheduler engine-call threads")
    # multi-replica tier (repro/serve/tier.py)
    ap.add_argument("--replicas", type=int, default=1,
                    help="> 1: serve through the replica-pool tier "
                         "(work-stealing engine replicas over a shared "
                         "model registry) instead of one MicroBatcher")
    ap.add_argument("--models", default=None,
                    help="comma-separated bundle paths to register and "
                         "serve CONCURRENTLY in one tier (names = file "
                         "stems); implies the tier path")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="tier admission bound: requests past this many "
                         "queued are rejected (or shed, per "
                         "--overload-policy) instead of queueing unboundedly")
    ap.add_argument("--overload-policy", choices=("reject", "shed-oldest"),
                    default="reject",
                    help="what happens at the --max-queue bound")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="default request deadline; the tier coalesces "
                         "batches from deadline buckets, soonest first")
    ap.add_argument("--require-fused", action="store_true",
                    help="fail loudly (exit) unless the engine compiled on "
                         "the fused shared-table path or better — an "
                         "EnginePathWarning downgrade to the generic path "
                         "cannot pass as a silent perf regression")
    ap.add_argument("--require-pallas", action="store_true",
                    help="imply --engine pallas and fail loudly unless the "
                         "single-launch Pallas mega-kernel actually compiled")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.obs import watch_gc
    enable_compile_cache()
    watch_gc()
    if args.require_pallas and args.engine == "float":
        args.engine = "pallas"
    if args.engine in ("tables", "pallas"):
        return serve_tables(args)
    if args.require_fused:
        ap.error("--require-fused only applies to --engine tables/pallas")
    if args.arch is None:
        ap.error("--arch is required with --engine float")

    from repro.configs.base import get_config, get_smoke
    from repro.models.registry import build_model
    from repro.nn.params import init_params

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = init_params(model.defs(), jax.random.PRNGKey(args.seed))

    total = args.prompt_len + args.gen
    rng = np.random.default_rng(args.seed)
    batch = {}
    for k, v in model.input_specs(args.prompt_len, args.batch, "prefill").items():
        if v.dtype == jnp.int32:
            batch[k] = jnp.asarray(
                rng.integers(1, cfg.vocab, (args.batch, args.prompt_len)), jnp.int32)
        else:
            batch[k] = jnp.asarray(rng.normal(0, 1, v.shape), v.dtype)

    t0 = time.time()
    prefill = jax.jit(model.prefill)
    logits, cache = prefill(params, batch)
    # grow KV caches from prompt_len to the full generation horizon
    grown = {}
    for k, v in cache.items():
        if hasattr(v, "ndim") and v.ndim == 5 and v.shape[3] == args.prompt_len:
            pad = [(0, 0)] * 5
            pad[3] = (0, total - args.prompt_len)
            grown[k] = jnp.pad(v, pad)
        else:
            grown[k] = v
    cache = grown
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    decode = jax.jit(model.decode_step, donate_argnums=(1,))
    tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out_tokens = [tokens]
    t0 = time.time()
    for _ in range(args.gen - 1):
        logits, cache = decode(params, cache, tokens)
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out_tokens.append(tokens)
    jax.block_until_ready(tokens)
    t_decode = time.time() - t0

    gen = np.stack([np.asarray(t) for t in out_tokens], axis=1)
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"prefill({args.prompt_len} tok)={t_prefill*1e3:.1f} ms  "
          f"decode={t_decode/max(args.gen-1,1)*1e3:.2f} ms/tok")
    print(f"[serve] sample generations (token ids): {gen[0][:12].tolist()}")


# --------------------------------------------------------------------------- #
# --engine tables: the compiled integer LUT artifact as the serving runtime
# --------------------------------------------------------------------------- #
def _build_model_program(args):
    """Lower the requested model spec to a DAIS program (untrained init)."""
    if args.model == "pid-hybrid":
        from repro.core.lower import lower
        from repro.models.pid import (build_pid_graph, build_pid_layers,
                                      init_pid_params)

        layers = build_pid_layers(hidden=args.lut_hidden)
        params = init_pid_params(layers, jax.random.PRNGKey(args.seed))
        graph = build_pid_graph(layers, n_samples=args.ctx)
        prog = lower(graph, [*params, None])
        return prog, f"model=pid-hybrid ctx={args.ctx}"
    if args.model == "jsc-plf":
        from repro.core.lower import lower
        from repro.models.plf import (build_plf_graph, build_plf_layers,
                                      init_plf_params)

        layers = build_plf_layers(hidden=args.lut_hidden)
        params = init_plf_params(layers, jax.random.PRNGKey(args.seed))
        prog = lower(build_plf_graph(layers), params)
        return prog, "model=jsc-plf particles=32"

    from repro.core.dais import compile_sequential
    from repro.core.lut_layers import LUTDense

    dims = [int(d) for d in args.lut_dims.split(",")]
    if len(dims) < 2:
        raise SystemExit("--lut-dims needs at least in,out (e.g. 16,5)")
    layers = [LUTDense(ci, co, hidden=args.lut_hidden, use_batchnorm=(k == 0))
              for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:]))]
    keys = jax.random.split(jax.random.PRNGKey(args.seed), len(layers))
    params = [l.init(k) for l, k in zip(layers, keys)]
    prog = compile_sequential(layers, params, args.in_f, args.in_i)
    return prog, f"model=lut-stack dims={dims}"


def _rtl_gate(args, prog, engine, *, oracle=None) -> dict:
    """Run the RTL attestation (``core.rtl.verify_rtl``) and report it."""
    from repro.core.rtl import verify_rtl

    t0 = time.time()
    att = verify_rtl(prog, oracle=oracle, engine=engine,
                     n_random=256 if args.smoke else 1024, seed=args.seed)
    print(f"[serve] rtl gate PASSED: {att['verdict']} over "
          f"{att['random']} random + {att['exhaustive']} exhaustive rows "
          f"({att['n_wires']} wires, verilog sha256 "
          f"{att['verilog_sha256'][:12]}, {time.time() - t0:.2f}s)")
    return att


def _spec(args, mesh, *, verify: str, optimize: bool = False):
    from repro.serve.api import EngineSpec

    prefer = "pallas" if (args.engine == "pallas"
                          or args.require_pallas) else None
    require = ("pallas" if args.require_pallas
               else "fused" if args.require_fused else None)
    return EngineSpec(engine=prefer, mesh=mesh, require=require,
                      verify=verify, optimize=optimize,
                      n_random=256 if args.smoke else 2048, seed=args.seed)


def _tables_engine(args, mesh):
    """Build (or cold-start) the verified integer engine per the CLI flags.

    Everything goes through the ``repro.serve.api`` façade — one
    :class:`EngineSpec` captures the preferred lowering, the require-flags,
    and the verify posture:

    * ``--artifact`` file exists → ``build(path, spec)`` loads the bundle
      (content-hash checked) and either re-runs the gate (``verify="full"``)
      or — with ``--skip-verify-cached`` — trusts the bundle's stored
      attestation (``verify="cached"``);
    * otherwise ``build(prog, spec)`` compiles from the model spec
      (``optimize=True`` under ``--dce``, gated against the unoptimized
      oracle) and, when ``--artifact`` is set, the bundle is saved for the
      next cold start.
    """
    from repro.serve.api import EngineRequirementError, build
    from repro.serve.artifact import save_artifact

    if args.artifact and os.path.exists(args.artifact):
        if args.dce:
            raise SystemExit(
                "--dce applies at compile time and cannot rewrite an "
                "existing bundle (its stages and attestation cover the "
                "stored program).  Delete the bundle (or point --artifact "
                "elsewhere) and re-run with --dce to save an optimized one.")
        spec = _spec(args, mesh,
                     verify="cached" if args.skip_verify_cached else "full")
        try:
            built = build(args.artifact, spec)
        except EngineRequirementError as e:
            raise SystemExit(str(e))
        engine, att = built.engine, built.attestation
        print(f"[serve] artifact loaded: {args.artifact} "
              f"(hash {built.content_hash[:12]}, path={engine.path}, "
              f"{built.timings['load_s'] + built.timings['compile_s']:.2f}s "
              f"— no re-lowering)")
        if "gate_s" in built.timings:
            print(f"[serve] bit-exact gate PASSED: {att['random']} random + "
                  f"{att['exhaustive']} exhaustive rows vs DaisProgram.run "
                  f"(gate {built.timings['gate_s']:.2f}s)")
        else:
            print(f"[serve] bit-exact gate SKIPPED: cached attestation "
                  f"({att.get('random')} random + {att.get('exhaustive')} "
                  f"exhaustive rows) verified by content hash")
        if args.lint:
            from repro.launch.lint import lint_program
            lint_program(built.prog, name=args.artifact)
        if args.verify_rtl:
            _rtl_gate(args, built.prog, engine)
        return built.prog, engine

    t0 = time.time()
    src_prog, model_desc = _build_model_program(args)
    t_lower = time.time() - t0
    if args.lint:
        from repro.launch.lint import lint_program
        lint_program(src_prog, name=model_desc)
    spec = _spec(args, mesh, verify="full", optimize=args.dce)
    try:
        built = build(src_prog, spec)
    except EngineRequirementError as e:
        raise SystemExit(str(e))
    prog, engine = built.prog, built.engine
    gate = dict(built.attestation)
    if args.dce:
        print(f"[serve] dce: {built.timings['dce_summary']}")
    if args.verify_rtl:
        # three-way attestation: the emitted Verilog (simulated) vs the
        # UNoptimized interpreter vs the engine — with --dce this proves
        # the optimized program's RTL against the pre-DCE oracle
        gate["rtl"] = _rtl_gate(args, prog, engine, oracle=built.oracle)
    pk = (f" launches={engine.n_launches} "
          f"packed_table_bytes={engine.packed_table_bytes}"
          if engine.path == "pallas" else "")
    print(f"[serve] engine=tables {model_desc} instrs={prog.n_instrs()} "
          f"path={engine.path} groups={engine.n_groups} "
          f"stages={','.join(engine.stage_kinds) or '-'} "
          f"dtype={np.dtype(engine.dtype).name} "
          f"mesh={tuple(mesh.devices.shape)}{pk}")
    print(f"[serve] bit-exact gate PASSED: {gate['random']} random + "
          f"{gate['exhaustive']} exhaustive rows vs DaisProgram.run "
          f"(lower {t_lower:.2f}s, gate {built.timings['gate_s']:.2f}s)")
    if args.artifact:
        digest = save_artifact(args.artifact, prog, attestation=gate)
        print(f"[serve] artifact saved: {args.artifact} "
              f"(hash {digest[:12]}, attestation stored)")
    return prog, engine


def serve_tables(args) -> None:
    from repro.kernels.lut_serve import input_code_bounds
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh()
    if args.models or args.replicas > 1:
        return serve_tier(args, mesh)
    prog, engine = _tables_engine(args, mesh)
    if args.serve_loop:
        return serve_loop(args, prog, engine)

    # one-shot request loop: run one pre-formed batch of random in-range
    # codes through the jitted integer engine, time the steady state
    lo, hi = input_code_bounds(prog)
    rng = np.random.default_rng(args.seed)
    codes = rng.integers(lo, hi + 1, (args.batch, engine.n_inputs), np.int64)
    jax.block_until_ready(engine.run(codes))        # compile + warm
    n_batches = max(args.gen, 1)
    t0 = time.time()
    for b in range(n_batches):
        out = engine.run(codes)
    jax.block_until_ready(out)
    dt = time.time() - t0
    rows_s = n_batches * args.batch / dt
    t0 = time.time()
    ref = prog.run(codes)
    t_interp = time.time() - t0
    assert np.array_equal(np.asarray(jax.device_get(out), np.int64), ref)
    print(f"[serve] {n_batches} batches x {args.batch} rows: "
          f"{dt / n_batches * 1e3:.2f} ms/batch  ({rows_s:,.0f} rows/s; "
          f"numpy interpreter {t_interp * 1e3:.2f} ms/batch)")
    print(f"[serve] sample output codes (grid f={engine.output_f}): "
          f"{np.asarray(out[0]).tolist()}")


def serve_loop(args, prog, engine) -> None:
    """Synthetic open-loop traffic through the micro-batching scheduler.

    ``repro.serve.scheduler.compare_under_load`` runs the identical driver
    twice — engine-backed, then numpy-interpreter-backed — so the reported
    comparison is service-path vs service-path (same coalescing, same
    buckets), not service vs one pre-formed batch, and asserts every
    response bit-exact against ``DaisProgram.run``.  Reports p50/p99
    request latency and achieved throughput for both.
    """
    from repro.kernels.lut_serve import input_code_bounds
    from repro.serve.scheduler import ServeConfig, compare_under_load

    n = max(args.requests, 1)
    lo, hi = input_code_bounds(prog)
    rng = np.random.default_rng(args.seed)
    codes = rng.integers(lo, hi + 1, (n, engine.n_inputs), np.int64)

    cfg = ServeConfig(max_batch=args.max_batch,
                      max_delay_ms=args.max_delay_ms,
                      n_workers=args.workers,
                      max_queue=args.max_queue,
                      overload_policy=args.overload_policy)
    print(f"[serve-loop] scheduler up: max_batch={cfg.max_batch} "
          f"deadline={cfg.max_delay_ms}ms workers={cfg.n_workers}")
    offered = (f"{args.rate:,.0f} req/s" if args.rate > 0
               else "max-rate burst")
    rows = {r["backend"]: r
            for r in compare_under_load(prog, engine, codes, cfg,
                                        rates=[args.rate])}
    for name, s in rows.items():
        print(f"[serve-loop] {name:>6}: {n} requests @ {offered}: "
              f"p50={s['p50_ms']:.2f} ms  p99={s['p99_ms']:.2f} ms  "
              f"throughput={s['rows_per_s']:,.0f} rows/s  "
              f"(batches={s['n_batches']}, "
              f"mean_fill={s['mean_batch_fill']:.1f}, "
              f"pad_overhead={s['pad_overhead'] * 100:.0f}%, "
              f"warmup {s['warmup_s']:.2f}s)")
    ratio = rows["engine"]["rows_per_s"] / rows["interp"]["rows_per_s"]
    print(f"[serve-loop] engine/interpreter throughput ratio: {ratio:.2f}x  "
          f"all {n} responses bit-exact vs DaisProgram.run")


def serve_tier(args, mesh) -> None:
    """Multi-replica, multi-model serving through the tier.

    ``--models a.npz,b.npz`` registers every bundle (names = file stems)
    into one :class:`~repro.serve.registry.ModelRegistry`; without it the
    single engine from the usual CLI flags serves as model ``"default"``.
    The open-loop driver then submits interleaved per-model traffic at
    ``--rate`` (0 = burst) and every response is asserted bit-exact against
    *that model's* ``DaisProgram.run`` — per-model correctness under
    concurrent multi-model load, not just aggregate counts.
    """
    from repro.kernels.lut_serve import input_code_bounds
    from repro.obs import gc_stats
    from repro.serve.api import build, tier_from_built
    from repro.serve.scheduler import RejectedError, ServeConfig
    from repro.serve.tier import TierConfig

    built = {}
    if args.models:
        spec = _spec(args, mesh,
                     verify="cached" if args.skip_verify_cached else "full")
        for path in args.models.split(","):
            name = os.path.splitext(os.path.basename(path))[0]
            built[name] = build(path, spec)
            print(f"[tier] registered {name!r}: hash "
                  f"{built[name].content_hash[:12]} "
                  f"path={built[name].engine.path}")
    else:
        prog, engine = _tables_engine(args, mesh)
        from repro.serve.api import BuiltEngine
        built["default"] = BuiltEngine(engine=engine, prog=prog, oracle=prog,
                                       attestation=None)

    cfg = TierConfig(
        n_replicas=args.replicas,
        serve=ServeConfig(max_batch=args.max_batch,
                          max_delay_ms=args.max_delay_ms,
                          max_queue=args.max_queue,
                          slo_ms=args.slo_ms,
                          overload_policy=args.overload_policy))
    tier = tier_from_built(built, cfg)
    # every replica thread runs the one engine built on `mesh`, whose
    # batches shard over all of the mesh's devices
    print(f"[tier] up: {args.replicas} replica thread(s) sharing each "
          f"model's engine on a {mesh.devices.size}-device mesh, "
          f"models={sorted(built)}, max_queue={args.max_queue}, "
          f"policy={args.overload_policy}")

    # interleaved per-model open-loop traffic, absolute-deadline paced
    n = max(args.requests, 1)
    rng = np.random.default_rng(args.seed)
    work = []                                  # (model, row, expected_row)
    per = max(n // len(built), 1)
    for name, b in built.items():
        lo, hi = input_code_bounds(b.prog)
        codes = rng.integers(lo, hi + 1, (per, b.engine.n_inputs), np.int64)
        ref = np.asarray(b.prog.run(codes), np.int64)
        work += [(name, codes[i], ref[i]) for i in range(per)]
    order = rng.permutation(len(work))
    gc0 = gc_stats()
    t0 = time.monotonic()
    flights, n_rejected = [], 0
    for k, idx in enumerate(order):
        name, row, ref = work[idx]
        if args.rate > 0:
            delay = (t0 + k / args.rate) - time.monotonic()
            if delay > 0:
                time.sleep(delay)
        try:
            flights.append((tier.submit(row, name), name, ref))
        except RejectedError:
            n_rejected += 1
    mismatches = 0
    for fut, name, ref in flights:
        if not np.array_equal(np.asarray(fut.result(timeout=120), np.int64),
                              ref):
            mismatches += 1
    wall = time.monotonic() - t0
    gc_pause_s = gc_stats().pause_s - gc0.pause_s
    s = tier.stats()
    tier.stop()
    if mismatches:
        raise SystemExit(f"[tier] {mismatches} responses diverged from "
                         f"their model's DaisProgram.run")
    offered = (f"{args.rate:,.0f} req/s" if args.rate > 0
               else "max-rate burst")
    print(f"[tier] {len(flights)} served @ {offered}: "
          f"p50={s.p50_ms:.2f} ms  p99={s.p99_ms:.2f} ms  "
          f"throughput={len(flights) / wall:,.0f} req/s  "
          f"(batches={s.n_batches}, stolen={s.n_stolen}, "
          f"rejected={n_rejected}, shed={s.n_shed}, "
          f"deadline_misses={s.deadline_misses})")
    print(f"[tier] mean queue wait={s.queue_wait_s / max(s.n_requests, 1) * 1e3:.3f} ms  "
          f"mean flush={s.flush_s / max(s.n_batches, 1) * 1e3:.3f} ms  "
          f"collector pauses={gc_pause_s * 1e3:.1f} ms")
    print(f"[tier] per-model: "
          f"{ {k: v for k, v in sorted(s.per_model.items())} } — every "
          f"response bit-exact vs its model's interpreter")


if __name__ == "__main__":
    main()
