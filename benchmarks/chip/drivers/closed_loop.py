"""Closed-loop bulk calls of ``BuiltEngine.engine.run``, one in flight.

The engine is built as ``launch/serve.py`` builds it: ``serve.api.build``
with the default gate, on a mesh over the cell's chips.  Each call takes
the next batch of a pool of distinct batches, waits for the device to
finish it, and fetches every output to the host: three spans, so that the
fetch times the copy alone.  Afterwards a sample of each pool batch's rows, drawn from the
seed and from every device's shard, with each shard's most active
waveform in it, is compared with the plain reference, in every call; and
every call's whole output must equal the first call's of the same batch.
"""

from __future__ import annotations

import time

import jax
import numpy as np


def setup(run):
    from repro.launch.mesh import make_mesh
    from repro.serve.api import EngineSpec, build

    from work import serve_work

    cfg, mix, model = run.cfg, run.traffic, run.model
    params = model.make_weights(cfg, run.seed, serve=True)
    run.mark("weights made")
    prog = model.lower(cfg, params)
    run.mark(f"lowered: {prog.n_instrs()} instructions")
    mesh = make_mesh((run.chips,), ("data",), devices=run.devices[:run.chips])
    built = build(prog, EngineSpec(mesh=mesh))
    engine = built.engine
    run.mark(f"built and gated: {built.timings}")
    rows = mix["rows_per_device"] * run.chips
    pool = model.request_codes(cfg, run.seed, mix["pool_batches"] * rows)
    pool = pool.reshape(mix["pool_batches"], rows, -1)
    run.mark("inputs made")
    np.asarray(engine.run(pool[0]))
    run.mark("call shape warm")
    run.work = serve_work(prog)
    return {"engine": engine, "pool": pool, "params": params, "outs": []}


def window(run, st) -> None:
    engine, pool, outs = st["engine"], st["pool"], st["outs"]
    call_s, wait_s, fetch_s = [], [], []
    t0 = time.monotonic()
    while True:
        b = len(outs) % len(pool)
        t1 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.engine_call"):
            out = engine.run(pool[b])
        t2 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.device_wait"):
            out.block_until_ready()
        t3 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench.fetch"):
            host = np.asarray(out)
        t4 = time.monotonic()
        outs.append(host)
        call_s.append(t2 - t1)
        wait_s.append(t3 - t2)
        fetch_s.append(t4 - t3)
        if t4 - t0 >= run.seconds:
            break
    rows = len(outs) * pool.shape[1]
    run.e2e["serve_rows_per_s"] = rows / (t4 - t0)
    run.spans.update(engine_call_s=call_s, device_wait_s=wait_s, fetch_s=fetch_s)
    run.counters.update(calls=len(outs), rows=rows, window_s=t4 - t0)


def sample_rows(seed: int, codes: np.ndarray, shards: int, per_shard: int):
    """Rows to compare: from each shard its most active row (largest sum of
    input codes) and ``per_shard - 1`` more drawn from the seed."""
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 11]))
    size = codes.shape[0] // shards
    picked = []
    for s in range(shards):
        lo = s * size
        busiest = lo + int(np.argmax(codes[lo:lo + size].sum(axis=1)))
        others = rng.choice(np.delete(np.arange(lo, lo + size), busiest - lo),
                            per_shard - 1, replace=False)
        picked += [busiest, *others]
    return np.sort(np.asarray(picked))


def check(run, st) -> None:
    pool, outs = st["pool"], st["outs"]
    st.pop("engine")
    ref = run.model.Reference(run.cfg, st["params"])
    wrong = inconsistent = 0
    for b in range(len(pool)):
        calls = [o for k, o in enumerate(outs) if k % len(pool) == b]
        if not calls:
            continue
        rows = sample_rows(run.seed + b, pool[b], run.chips,
                           run.traffic["check_rows_per_device"])
        lo, hi = ref(pool[b][rows])
        for o in calls:
            got = np.asarray(o, np.int64)
            wrong += int(np.any((got[rows] < lo) | (got[rows] > hi), axis=1).sum())
            inconsistent += int(np.any(got != calls[0], axis=1).sum())
    run.attempted = len(outs)
    run.failed = 0
    run.checks["wrong_rows"] = wrong
    run.checks["inconsistent_rows"] = inconsistent
