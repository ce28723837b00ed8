"""Scan-chunked training driver + async prefetcher tests.

The load-bearing claim: grouping optimizer steps into jitted ``lax.scan``
chunks (train/loop.py) and moving batch synthesis onto the prefetch
thread (data/pipeline.py) change not one bit of the resulting params or
optimizer state vs the per-step jitted loop — including across mixed
chunk lengths, grouping choices, and crash/resume from a checkpoint at a
step that is NOT chunk-aligned.
"""

import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.pipeline import HostPrefetcher, chunk_stream, stack_batches
from repro.train.loop import chunked_train, plan_chunks, run_chunked

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
ENV.pop("XLA_FLAGS", None)


# ---------------------------------------------------------------- planning
def test_plan_chunks_partitions_range():
    segs = plan_chunks(0, 20, 8)
    assert segs == [(0, 8), (8, 8), (16, 4)]
    # exact cover: consecutive, no gaps, no overlap
    step = 0
    for s, k in segs:
        assert s == step and k >= 1
        step += k
    assert step == 20


def test_plan_chunks_respects_boundaries():
    segs = plan_chunks(0, 12, 4, boundaries=[6, 7])
    # no segment may cross 6 or 7; every boundary is a segment end
    ends = {s + k for s, k in segs}
    assert {6, 7, 12} <= ends
    for s, k in segs:
        assert k <= 4
        for b in (6, 7):
            assert not (s < b < s + k), f"segment ({s},{k}) crosses {b}"


def test_plan_chunks_ignores_out_of_range_boundaries():
    assert plan_chunks(5, 9, 10, boundaries=[0, 5, 9, 40]) == [(5, 4)]


def test_plan_chunks_resume_from_unaligned_start():
    # resuming at step 5 (mid-way through what a fresh run would chunk as
    # [4, 8)) still covers [5, 12) exactly
    segs = plan_chunks(5, 12, 4, boundaries=[3, 6, 9])
    assert segs == [(5, 1), (6, 3), (9, 3)]


def test_plan_chunks_validates():
    with pytest.raises(ValueError, match="chunk_steps"):
        plan_chunks(0, 10, 0)
    with pytest.raises(ValueError, match="empty"):
        plan_chunks(10, 5, 4)
    assert plan_chunks(5, 5, 4) == []


# ------------------------------------------------------------- prefetcher
def _toy_get_batch(step: int) -> dict:
    rng = np.random.default_rng([11, step])
    return {"x": rng.normal(0, 1, (4, 3)).astype(np.float32),
            "y": np.full((4,), step, np.int32)}


def test_stack_batches_leading_axis():
    chunk = stack_batches(_toy_get_batch, 2, 3)
    assert chunk["x"].shape == (3, 4, 3)
    np.testing.assert_array_equal(chunk["y"][:, 0], [2, 3, 4])
    with pytest.raises(ValueError, match="chunk length"):
        stack_batches(_toy_get_batch, 0, 0)


def test_prefetch_chunks_bit_identical_to_sync():
    segs = plan_chunks(0, 13, 4, boundaries=[6])
    sync = list(chunk_stream(_toy_get_batch, segs, prefetch=False))
    pre = list(chunk_stream(_toy_get_batch, segs, prefetch=True))
    assert [(s, k) for s, k, _ in sync] == [(s, k) for s, k, _ in pre]
    for (_, _, a), (_, _, b) in zip(sync, pre):
        for key in a:
            np.testing.assert_array_equal(np.asarray(a[key]),
                                          np.asarray(b[key]))


def test_prefetcher_preserves_stateful_rng_order():
    """A stateful host RNG drawn once per get_batch (the Pareto sweep's
    pattern) must see the same call order on the worker thread."""
    def make(seed):
        rng = np.random.default_rng(seed)
        return lambda step: {"idx": rng.integers(0, 1000, 8)}

    segs = plan_chunks(0, 10, 3)
    sync = list(chunk_stream(make(5), segs, prefetch=False))
    pre = list(chunk_stream(make(5), segs, prefetch=True))
    for (_, _, a), (_, _, b) in zip(sync, pre):
        np.testing.assert_array_equal(np.asarray(a["idx"]),
                                      np.asarray(b["idx"]))


def _prefetch_threads():
    return [t for t in threading.enumerate() if t.name == "host-prefetch"]


def test_prefetcher_clean_shutdown_mid_stream():
    """Abandoning the stream early leaks no thread and no queued chunk."""
    segs = plan_chunks(0, 40, 2)   # far more chunks than we consume
    pf = HostPrefetcher(_toy_get_batch, segs, depth=2)
    it = iter(pf)
    next(it)
    pf.close()
    assert not pf._thread.is_alive()
    assert pf._q.qsize() == 0      # queued device buffers were drained
    pf.close()                     # idempotent
    assert not _prefetch_threads()


def test_chunk_stream_generator_abandonment_joins_worker():
    segs = plan_chunks(0, 40, 2)
    gen = chunk_stream(_toy_get_batch, segs, prefetch=True)
    next(gen)
    gen.close()                    # GeneratorExit → context __exit__ → close
    assert not _prefetch_threads()


def test_prefetcher_propagates_get_batch_error():
    def bad(step: int) -> dict:
        if step == 3:
            raise RuntimeError("synth failed at step 3")
        return _toy_get_batch(step)

    segs = plan_chunks(0, 10, 2)
    with pytest.raises(RuntimeError, match="synth failed"):
        list(chunk_stream(bad, segs, prefetch=True))
    assert not _prefetch_threads()


# --------------------------------------------------- chunked == per-step
def _lut_setup(dims=(6, 5, 3), hidden=3, batch=16):
    from repro.core.lut_layers import LUTDense
    from repro.optim.adam import AdamConfig
    from repro.train.steps import TrainHParams, make_lut_train_step

    layers = [LUTDense(ci, co, hidden=hidden, use_batchnorm=(k == 0))
              for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:]))]
    hp = TrainHParams(adam=AdamConfig(lr=1e-3))
    raw_step, init_fn = make_lut_train_step(layers, hp, jit=False)

    def get_batch(step: int) -> dict:
        rng = np.random.default_rng([23, step])
        return {"x": rng.normal(0, 1, (batch, dims[0])).astype(np.float32),
                "y": rng.integers(0, dims[-1], batch).astype(np.int32)}

    return raw_step, init_fn, get_batch


def _assert_trees_equal(a, b, tag):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), tag
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=tag)


def test_chunked_bit_exact_vs_per_step():
    """Mixed chunk lengths + prefetch thread vs per-step jit: every bit of
    params AND optimizer state identical (BN moving stats included —
    layer 0 carries batchnorm)."""
    raw_step, init_fn, get_batch = _lut_setup()
    steps = 11

    step_fn = jax.jit(raw_step)
    p_ref, o_ref = init_fn(jax.random.PRNGKey(0))
    for s in range(steps):
        p_ref, o_ref, _ = step_fn(p_ref, o_ref,
                                  {k: jnp.asarray(v)
                                   for k, v in get_batch(s).items()})

    p0, o0 = init_fn(jax.random.PRNGKey(0))
    p_chk, o_chk, metrics = run_chunked(raw_step, p0, o0, get_batch,
                                        0, steps, chunk_steps=4,
                                        boundaries=[6], prefetch=True)
    _assert_trees_equal(p_ref, p_chk, "params")
    _assert_trees_equal(o_ref, o_chk, "opt_state")
    assert metrics["loss"].shape == (1,)   # last chunk: step 10 alone


def test_chunk_grouping_invariance():
    """Chunking as 3s vs 7s is pure launch-granularity: same params."""
    raw_step, init_fn, get_batch = _lut_setup()
    outs = []
    for chunk in (3, 7):
        p0, o0 = init_fn(jax.random.PRNGKey(1))
        p, o, _ = run_chunked(raw_step, p0, o0, get_batch, 0, 14,
                              chunk_steps=chunk, prefetch=(chunk == 3))
        outs.append((p, o))
    _assert_trees_equal(outs[0][0], outs[1][0], "params")
    _assert_trees_equal(outs[0][1], outs[1][1], "opt_state")


def test_chunked_train_yields_real_boundaries():
    raw_step, init_fn, get_batch = _lut_setup()
    p, o = init_fn(jax.random.PRNGKey(0))
    results = list(chunked_train(raw_step, p, o, get_batch, 0, 10,
                                 chunk_steps=4, prefetch=False))
    assert [(r.step, r.k) for r in results] == [(0, 4), (4, 4), (8, 2)]
    # first occurrence of each k is compile-inclusive; repeats are not
    assert [r.compiled for r in results] == [True, False, True]
    assert all(r.dt_s > 0 for r in results)
    for r in results:
        assert set(r.metrics) >= {"loss", "ce", "ebops"}
        assert r.metrics["loss"].shape == (r.k,)


@pytest.mark.parametrize("prefetch", [False, True])
def test_chunk_results_time_the_feed_wait_and_the_dispatch(prefetch):
    raw_step, init_fn, get_batch = _lut_setup()
    p, o = init_fn(jax.random.PRNGKey(0))
    results = list(chunked_train(raw_step, p, o, get_batch, 0, 10,
                                 chunk_steps=4, prefetch=prefetch))
    assert len(results) == 3
    for r in results:
        assert r.wait_s >= 0 and r.dispatch_s >= 0
        assert r.dispatch_s <= r.dt_s       # dt_s is dispatch + metrics pull
    assert not _prefetch_threads()          # the stream closed with the loop


@pytest.mark.slow
def test_train_launcher_chunked_crash_resume_vs_per_step(tmp_path):
    """Crash at step 5 — NOT aligned to --chunk-steps 4 — then resume;
    final checkpoint must be bit-identical to a straight per-step run
    (--chunk-steps 1 --no-prefetch).  Proves the crash boundary splits a
    chunk, resume replans from an unaligned start, and the chunked loop
    is bit-exact against per-step on the full LM model."""
    ckpt_a = str(tmp_path / "a")
    ckpt_b = str(tmp_path / "b")
    base = [sys.executable, "-m", "repro.launch.train", "--arch", "olmo_1b",
            "--smoke", "--batch", "2", "--seq", "32", "--ckpt-every", "3",
            "--log-every", "100", "--steps", "12"]
    chunked = base + ["--chunk-steps", "4", "--ckpt-dir", ckpt_a]
    r = subprocess.run(chunked + ["--simulate-crash", "5"],
                       env=ENV, cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 17, r.stderr[-2000:]
    assert "simulating crash at step 5" in r.stdout
    r = subprocess.run(chunked, env=ENV, cwd=REPO,
                       capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "resumed from step 5" in r.stdout

    r2 = subprocess.run(base + ["--chunk-steps", "1", "--no-prefetch",
                                "--ckpt-dir", ckpt_b],
                        env=ENV, cwd=REPO, capture_output=True, text=True)
    assert r2.returncode == 0, r2.stderr[-2000:]

    za = np.load(os.path.join(ckpt_a, "step_0000000012.npz"))
    zb = np.load(os.path.join(ckpt_b, "step_0000000012.npz"))
    assert sorted(za.files) == sorted(zb.files)
    for k in za.files:
        np.testing.assert_array_equal(za[k], zb[k], err_msg=k)
