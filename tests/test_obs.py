"""The program's spans and counters: the collector hook, the serving tier's
queue and flush seconds, the engine's per-handle calls, the named device
scopes, and the nesting of the tier's spans in a profiler trace."""

import gc
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import GC_SPAN, GcStats, gc_stats, watch_gc


def _lut_program(dims=(6, 5, 3), seed=0):
    from repro.core.dais import compile_sequential
    from repro.core.lut_layers import LUTDense

    layers = [LUTDense(ci, co, hidden=4, use_batchnorm=(k == 0))
              for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:]))]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(layers))
    return compile_sequential(layers, [l.init(k) for l, k in zip(layers, keys)], 4, 2)


def _events(trace_dir):
    """Host events of the newest trace: (start_ns, end_ns, name, line)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    data = ProfileData.from_file(path)
    return [(ev.start_ns, ev.end_ns, ev.name, line.name)
            for plane in data.planes for line in plane.lines
            for ev in line.events]


# ------------------------------------------------------------------ collector
def test_watch_gc_is_idempotent_and_counts_a_full_collection():
    watch_gc()
    watch_gc()
    hooks = [cb for cb in gc.callbacks if type(cb).__name__ == "_GcWatch"]
    assert len(hooks) == 1
    before = gc_stats()
    gc.collect(2)
    after = gc_stats()
    assert after.passes[2] == before.passes[2] + 1
    assert after.seconds[2] > before.seconds[2]
    assert after.pause_s > before.pause_s
    assert all(a >= b for a, b in zip(after.passes, before.passes))


def test_gc_stats_sum_the_generations():
    s = GcStats(passes=(3, 1, 1), seconds=(0.001, 0.002, 0.05))
    assert s.pause_s == 0.001 + 0.002 + 0.05
    assert GcStats().pause_s == 0.0


def test_collector_pass_is_a_span_in_the_trace(tmp_path):
    watch_gc()
    jax.profiler.start_trace(str(tmp_path))
    try:
        gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    assert GC_SPAN in {name for _, _, name, _ in _events(str(tmp_path))}


# --------------------------------------------------------------------- engine
def test_engine_clones_count_their_own_calls():
    from repro.kernels.lut_serve import compile_program

    engine = compile_program(_lut_program())
    x = np.zeros((4, engine.n_inputs), np.int64)
    jax.block_until_ready(engine.run(x))
    a, b = engine.clone(), engine.clone()
    assert (a.n_calls, a.place_s) == (0, 0.0)
    for _ in range(3):
        jax.block_until_ready(a.run(x))
    jax.block_until_ready(b.run(x))
    assert (engine.n_calls, a.n_calls, b.n_calls) == (1, 3, 1)
    assert a.place_s > 0 and b.place_s > 0


def test_fused_stages_and_train_layers_carry_named_scopes():
    from repro.core.lut_layers import LUTDense
    from repro.kernels.lut_serve import compile_program
    from repro.optim.adam import adam_init
    from repro.train.steps import make_lut_train_step

    engine = compile_program(_lut_program())
    assert engine.path == "fused"
    text = engine._runner.lower(jnp.zeros((8, engine.n_inputs), engine.dtype)
                                ).as_text(debug_info=True)
    assert "stage0_lut" in text and "stage1_lut" in text

    layers = [LUTDense(6, 5, hidden=3, use_batchnorm=True), LUTDense(5, 3, hidden=3)]
    step, init = make_lut_train_step(layers, jit=False)
    params, opt = init(jax.random.PRNGKey(0))
    batch = {"x": jnp.zeros((8, 6)), "y": jnp.zeros((8,), jnp.int32)}
    text = jax.jit(step).lower(params, opt, batch).as_text(debug_info=True)
    assert "l0_LUTDense" in text and "l1_LUTDense" in text


# ----------------------------------------------------------------------- tier
def test_profiled_tier_nests_flush_engine_run_and_fetch(tmp_path):
    from repro.serve.api import EngineSpec, build, tier_from_built
    from repro.serve.scheduler import ServeConfig
    from repro.serve.tier import TierConfig

    prog = _lut_program()
    built = build(prog, EngineSpec(n_random=16))
    tier = tier_from_built({"m": built}, TierConfig(
        n_replicas=1, serve=ServeConfig(max_batch=8, max_delay_ms=1.0)))
    rows = np.zeros((6, built.engine.n_inputs), np.int64)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for row in rows:
            tier.submit(row).result(timeout=60)
    finally:
        jax.profiler.stop_trace()
        tier.stop()
    events = _events(str(tmp_path))
    flushes = [e for e in events if e[2] == "hgq.tier.flush"]
    assert flushes
    for s, e, _, line in flushes:
        inside = sorted((es, name) for es, ee, name, ln in events
                        if ln == line and s <= es and ee <= e
                        and name in ("hgq.tier.pack", "hgq.engine.run",
                                     "hgq.engine.place", "hgq.tier.fetch",
                                     "hgq.tier.resolve"))
        assert [name for _, name in inside] == [
            "hgq.tier.pack", "hgq.engine.run", "hgq.engine.place",
            "hgq.tier.fetch", "hgq.tier.resolve"]
    names = {name for _, _, name, _ in events}
    assert "hgq.tier.idle" in names or "hgq.tier.coalesce" in names
