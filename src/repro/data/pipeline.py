"""Async host→device input pipeline: batch synthesis off the critical path.

The scan-chunked training driver (``train/loop.py``) consumes *chunks* — K
per-step batches stacked along a new leading axis — one device transfer and
one jitted call per chunk.  This module builds those chunks, either
synchronously or on a background prefetch thread:

* :func:`stack_batches` — synthesize K host batches and stack their leaves;
* :class:`HostPrefetcher` — a double-buffered worker thread that runs the
  numpy synthesis (``get_batch``) *and* the ``jax.device_put`` for chunk
  N+1 while the device is still executing chunk N, so per-step host work
  (e.g. ``data/synthetic.py`` generators, modality-stub RNG) never sits on
  the training critical path;
* :func:`chunk_stream` — one generator over both modes.

Determinism contract: ``get_batch(step)`` must be a pure function of the
step index (plus whatever seed/host id it closes over) — the pipeline only
changes *where and when* batches are built, never *which* batches.  The
prefetcher calls ``get_batch`` strictly in step order on a single worker
thread, so even a stateful host RNG drawn once per step (as the Pareto
sweep does) sees the exact sequence the synchronous loop would.  The same
segments therefore always produce bit-identical chunks
(tests/test_train_loop.py).

Shutdown contract: :meth:`HostPrefetcher.close` (or leaving the context
manager / abandoning :func:`chunk_stream`) always stops and joins the
worker and drains queued device buffers — no leaked thread, no stranded
chunk, including when ``get_batch`` raises (the exception is re-raised in
the consumer).
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Sequence, Tuple

import jax
import numpy as np
from jax.profiler import TraceAnnotation


def stack_batches(get_batch: Callable[[int], dict], step: int, k: int):
    """K consecutive host batches stacked into one chunk pytree.

    Every leaf gains a leading axis of length ``k`` — the axis
    ``jax.lax.scan`` consumes in the chunked train step.
    """
    if k < 1:
        raise ValueError(f"chunk length must be >= 1, got {k}")
    batches = [get_batch(step + i) for i in range(k)]
    return jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                        *batches)


class HostPrefetcher:
    """Background double-buffered chunk builder.

    ``segments`` is the chunk plan — ``(first_step, k)`` pairs, typically
    from ``train/loop.plan_chunks``.  ``depth`` bounds how many finished
    chunks may wait device-resident ahead of the consumer (2 = classic
    double buffering: one in flight, one ready).  The worker opens the
    profiler spans ``hgq.prefetch.stack``, ``hgq.prefetch.put_device`` and
    ``hgq.prefetch.full`` (waiting for room in the queue).
    """

    _DONE = ("done", None)

    def __init__(self, get_batch: Callable[[int], dict],
                 segments: Iterable[Tuple[int, int]], depth: int = 2,
                 to_device: bool = True):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        self._get_batch = get_batch
        self._segments = list(segments)
        self._to_device = to_device
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._closed = False
        self._thread = threading.Thread(target=self._work,
                                        name="host-prefetch", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- worker
    def _put(self, item) -> bool:
        """Enqueue, but never block past a stop request."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def _work(self) -> None:
        try:
            for step, k in self._segments:
                if self._stop.is_set():
                    return
                with TraceAnnotation("hgq.prefetch.stack"):
                    chunk = stack_batches(self._get_batch, step, k)
                if self._to_device:
                    with TraceAnnotation("hgq.prefetch.put_device"):
                        chunk = jax.device_put(chunk)
                with TraceAnnotation("hgq.prefetch.full"):
                    if not self._put(("chunk", (step, k, chunk))):
                        return
        except BaseException as exc:  # noqa: BLE001 — re-raised in the consumer
            self._put(("error", exc))
        else:
            self._put(self._DONE)

    # ----------------------------------------------------------- consumer
    def __iter__(self) -> Iterator[Tuple[int, int, dict]]:
        while True:
            try:
                kind, payload = self._q.get(timeout=0.1)
            except queue.Empty:
                if not self._thread.is_alive():
                    # defensive: a worker can only vanish without a terminal
                    # item if close() raced us — stop iterating either way
                    return
                continue
            if kind == "chunk":
                yield payload
            elif kind == "error":
                self.close()
                raise payload
            else:  # done
                return

    # ------------------------------------------------------------ cleanup
    def close(self) -> None:
        """Stop the worker, join it, drop any queued chunks.  Idempotent."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        self._drain()
        self._thread.join(timeout=30.0)
        self._drain()  # the worker may have slipped one item in before exiting

    def _drain(self) -> None:
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                return

    def __enter__(self) -> "HostPrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def chunk_stream(get_batch: Callable[[int], dict],
                 segments: Sequence[Tuple[int, int]], prefetch: bool = True,
                 depth: int = 2) -> Iterator[Tuple[int, int, dict]]:
    """Yield ``(first_step, k, device_chunk)`` for each planned segment.

    ``prefetch=True`` routes through :class:`HostPrefetcher`; ``False`` is
    the synchronous fallback (identical chunks, host work on the critical
    path) used by ``--no-prefetch`` and as the benchmark baseline.
    """
    if not prefetch:
        for step, k in segments:
            yield step, k, jax.device_put(stack_batches(get_batch, step, k))
        return
    with HostPrefetcher(get_batch, segments, depth=depth) as pf:
        yield from pf
