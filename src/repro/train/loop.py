"""Scan-chunked training driver: K optimizer steps per jitted call.

The per-step loop (one jitted dispatch per Python iteration, synchronous
numpy batch synthesis, a device→host metrics pull whenever anything is
logged) pays per-step overhead that dwarfs the compute of the small LUT
models this repo trains — the regime where the paper's ">100× faster
LUT-aware training" claim lives.  This driver removes it structurally:

* **one launch per chunk** — :func:`make_chunked_step` wraps the *raw*
  (un-jitted) step function from ``train/steps.py`` into a single jitted
  ``jax.lax.scan`` over a stacked K-step batch chunk.  The ``(params,
  opt_state)`` carry is donated, so parameter/optimizer buffers are reused
  in place across the whole chunk.  β and lr schedules already read
  ``opt_state["step"]``, so scanning needs no new plumbing;
* **on-device metrics** — the scan stacks every step's metrics on device;
  the host sees ONE transfer per chunk (a ``(k,)`` array per metric), not
  one per step;
* **async host prefetch** — batch synthesis and ``device_put`` for chunk
  N+1 run on a background thread (``data/pipeline.py``) while chunk N
  computes, keeping per-step host work off the critical path;
* **boundary-exact planning** — :func:`plan_chunks` never lets a chunk
  cross a checkpoint / crash / snapshot boundary, so checkpoint cadence,
  ``--simulate-crash`` semantics and bit-exact resume are preserved.

Bit-exactness: grouping steps into scan chunks does not change a single
bit of the resulting params or optimizer state — the scan body is the same
traced computation as the per-step jit, applied in the same order.  This
is asserted by tests/test_train_loop.py and re-asserted on every
``benchmarks/train_bench.py`` run (BENCH_train.json), including across
mixed chunk lengths and restarts from mid-chunk checkpoints.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Iterable, Iterator, List, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation


def plan_chunks(start: int, stop: int, chunk_steps: int,
                boundaries: Iterable[int] = ()) -> List[Tuple[int, int]]:
    """Split steps ``[start, stop)`` into ``(first_step, k)`` segments.

    Each segment runs ``k <= chunk_steps`` consecutive steps and never
    crosses a boundary step, so host-visible side effects pinned to
    boundaries (checkpoint saves, simulated crashes, β-sweep snapshots)
    land at exactly the same step indices as a per-step loop.  Resuming
    from an arbitrary ``start`` (e.g. a checkpoint mid-way through what a
    fresh run would have chunked differently) is safe: chunk grouping does
    not affect the math, only the launch count.
    """
    if chunk_steps < 1:
        raise ValueError(f"chunk_steps must be >= 1, got {chunk_steps}")
    if stop < start:
        raise ValueError(f"empty step range [{start}, {stop})")
    cuts = sorted({b for b in boundaries if start < b < stop})
    segments: List[Tuple[int, int]] = []
    step = start
    while step < stop:
        next_cut = next((b for b in cuts if b > step), stop)
        k = min(chunk_steps, next_cut - step)
        segments.append((step, k))
        step += k
    return segments


def make_chunked_step(step_fn: Callable, donate: bool = True) -> Callable:
    """Jitted ``chunk_fn(params, opt_state, batches)`` scanning ``step_fn``.

    ``step_fn(params, opt_state, batch)`` is the raw step from
    ``make_train_step(..., jit=False)`` / ``make_lut_train_step(...,
    jit=False)`` (an already-jitted step also works — jit-under-jit
    inlines).  ``batches`` is a pytree whose leaves carry a leading chunk
    axis of length k; metrics come back stacked ``(k, ...)`` on device.
    Compiles once per distinct k — :func:`plan_chunks` produces at most a
    handful of lengths.
    """

    def chunk_fn(params, opt_state, batches):
        def body(carry, batch):
            p, o = carry
            p, o, metrics = step_fn(p, o, batch)
            return (p, o), metrics

        (params, opt_state), metrics = jax.lax.scan(
            body, (params, opt_state), batches)
        return params, opt_state, metrics

    return jax.jit(chunk_fn, donate_argnums=(0, 1) if donate else ())


@dataclasses.dataclass
class ChunkResult:
    """One executed chunk: new state + host-side stacked metrics."""

    step: int                       # first step index in the chunk
    k: int                          # steps executed ([step, step + k))
    params: Any
    opt_state: Any
    metrics: Dict[str, np.ndarray]  # each metric stacked to shape (k, ...)
    dt_s: float                     # wall time, dispatch → host-visible
    compiled: bool                  # first use of this k: compile-inclusive
    wait_s: float = 0.0             # waiting on the feed for this chunk
    dispatch_s: float = 0.0         # the chunk_fn call: donation, enqueue


def chunked_train(step_fn: Callable, params, opt_state,
                  get_batch: Callable[[int], dict], start: int, stop: int, *,
                  chunk_steps: int = 8, boundaries: Iterable[int] = (),
                  prefetch: bool = True, prefetch_depth: int = 2,
                  donate: bool = True) -> Iterator[ChunkResult]:
    """Drive ``step_fn`` over steps ``[start, stop)`` in scan chunks.

    Yields a :class:`ChunkResult` after each chunk *completes on device*
    (the metrics transfer blocks, so ``dt_s`` measures real compute
    boundaries — not async dispatch).  ``get_batch(step)`` returns the
    host-side numpy batch for one step and runs on the prefetch thread
    when ``prefetch=True``.  With ``donate=True`` the previous chunk's
    params/opt buffers are donated — hold only the latest ``ChunkResult``'s
    state.  Each chunk opens the profiler spans ``hgq.train.wait_chunk``,
    ``hgq.train.dispatch`` and ``hgq.train.pull``; the first two are also
    timed into ``wait_s`` and ``dispatch_s``.
    """
    from repro.data.pipeline import chunk_stream

    chunk_fn = make_chunked_step(step_fn, donate=donate)
    segments = plan_chunks(start, stop, chunk_steps, boundaries)
    seen_lengths: set = set()
    stream = chunk_stream(get_batch, segments, prefetch=prefetch,
                          depth=prefetch_depth)
    try:
        while True:
            t_wait = time.perf_counter()
            with TraceAnnotation("hgq.train.wait_chunk"):
                item = next(stream, None)
            if item is None:
                return
            step, k, batches = item
            compiled = k not in seen_lengths
            seen_lengths.add(k)
            t0 = time.perf_counter()
            with TraceAnnotation("hgq.train.dispatch"):
                params, opt_state, metrics = chunk_fn(params, opt_state, batches)
            t1 = time.perf_counter()
            # ONE device→host transfer per chunk; blocks until the scan is
            # done, which is what makes dt_s a real (watchdog-usable) boundary
            with TraceAnnotation("hgq.train.pull"):
                metrics = {name: np.asarray(v) for name, v in metrics.items()}
            dt_s = time.perf_counter() - t0
            yield ChunkResult(step, k, params, opt_state, metrics, dt_s,
                              compiled, wait_s=t0 - t_wait, dispatch_s=t1 - t0)
    finally:
        stream.close()


def run_chunked(step_fn: Callable, params, opt_state,
                get_batch: Callable[[int], dict], start: int, stop: int,
                on_chunk: Callable[[ChunkResult], None] = None,
                **kwargs) -> Tuple[Any, Any, Dict[str, np.ndarray]]:
    """Convenience wrapper over :func:`chunked_train`.

    Returns ``(params, opt_state, last_metrics)`` after the final chunk;
    ``on_chunk`` (if given) fires once per completed chunk.
    """
    metrics: Dict[str, np.ndarray] = {}
    for res in chunked_train(step_fn, params, opt_state, get_batch,
                             start, stop, **kwargs):
        params, opt_state, metrics = res.params, res.opt_state, res.metrics
        if on_chunk is not None:
            on_chunk(res)
    return params, opt_state, metrics
