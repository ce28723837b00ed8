"""Program spans in a trace: the sweep that attributes idle gaps, and the
counters ``breakdown.py`` reads over a window."""

import os

import numpy as np
import pytest

import breakdown
import cells
import harness
import spans
import work

HERE = os.path.dirname(os.path.abspath(__file__))
trace = harness.load_module(os.path.join(os.path.dirname(HERE), "trace.py"), "bench_trace")

# the recorded window of test_bench_trace.py, with the program's spans
# nested in the benchmark's: the engine call places and dispatches, and
# the fetch holds the tier's flush, its fetch and the collector
DEVICES = {
    "/device:TPU:0": [(1.0, 1.4, "fusion.1"), (1.2, 1.6, "fusion.2"),
                      (2.0, 2.5, "fusion.1"), (0.5, 1.1, "copy")],
    "/device:TPU:1": [(1.0, 2.0, "fusion.1")],
}
BENCH = [(1.0, 3.0, "bench.window"), (1.0, 1.5, "bench.engine_call"),
         (1.5, 2.9, "bench.fetch"), (0.0, 0.9, "bench.setup")]
PROGRAM = [(1.55, 2.8, "hgq.tier.flush"), (1.6, 1.95, "hgq.tier.fetch"),
           (2.55, 2.7, "hgq.gc"), (1.0, 1.1, "hgq.engine.place")]


def test_sweep_attributes_like_the_per_gap_scan():
    rng = np.random.default_rng(5)
    for _ in range(20):
        starts = rng.uniform(0, 10, 60)
        widths = rng.choice([0.5, 1.0, 2.0, 3.0], 60)   # ties on purpose
        names = ["bench.window" if i % 13 == 0 else f"bench.s{i % 7}"
                 for i in range(60)]
        host = [(s, s + w, n) for s, w, n in zip(starts, widths, names)]
        host.append((0.0, 20.0, "bench.window"))
        points = list(rng.uniform(-1, 14, 40)) + [host[3][0], host[5][1]]
        assert spans.innermost(host, points) == [trace._innermost(host, t)
                                                 for t in points]


def test_reduce_keeps_busy_window_and_ops_of_the_bench_reduction():
    plain = trace.reduce(DEVICES, BENCH)
    ours = spans.reduce(DEVICES, BENCH + PROGRAM)
    for key in ("busy_s", "window_s", "per_device_busy_s"):
        assert ours[key] == plain[key]
    assert ours["breakdown"]["device_ops"] == plain["breakdown"]["device_ops"]
    # with the benchmark's spans alone the gaps are attributed identically
    assert spans.reduce(DEVICES, BENCH)["breakdown"] == plain["breakdown"]


def test_gaps_go_to_the_innermost_program_span():
    gaps = dict(spans.reduce(DEVICES, BENCH + PROGRAM)["breakdown"]["idle_gaps"])
    # device 0 idles [1.6, 2.0] (middle 1.8: the tier's fetch) and
    # [2.5, 3.0] (middle 2.75: the flush; the gc span has ended); device 1
    # idles [2.0, 3.0] (middle 2.5: the flush); averaged over two devices
    assert gaps == pytest.approx({"hgq.tier.fetch": 0.4 / 2,
                                  "hgq.tier.flush": (0.5 + 1.0) / 2})
    assert "bench.fetch" not in gaps and "idle" not in gaps


def test_a_gap_outside_every_span_is_idle():
    host = [(0.0, 4.0, "bench.window"), (0.0, 1.0, "hgq.tier.flush")]
    out = spans.reduce({"/device:TPU:0": [(0.0, 1.0, "fusion.1")]}, host)
    assert out["breakdown"]["idle_gaps"] == [["idle", 3.0]]


def test_load_reads_program_spans_of_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x) * 2)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("hgq.tier.flush"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    devs, host = spans.load(str(tmp_path), "/device:TPU:", 1)
    assert {"bench.window", "hgq.tier.flush"} <= {h[2] for h in host}
    assert devs == {}                    # no TPU plane on the CPU


# ------------------------------------------------------------------ counters
WINDOW = {"tier_requests": 400, "tier_batches": 25, "tier_queue_wait_s": 0.8,
          "tier_flush_s": 0.05, "engine_calls": 4, "engine_place_s": 0.02,
          "train_chunks": 10, "train_wait_s": 0.003, "train_dispatch_s": 0.01,
          "gc_pause_s": 0.12}


@pytest.mark.parametrize("metric, value", [
    ("tier_queue_ms.stream", 2.0),
    ("tier_flush_ms.stream", 2.0),
    ("engine_place_ms.bulk", 5.0),
    ("train_feed_wait_ms", 0.3),
    ("train_dispatch_ms", 1.0),
    ("gc_pause_ms", 120.0),
])
def test_counter_metrics_read_the_window(metric, value):
    assert breakdown.COUNTERS[metric](WINDOW) == pytest.approx(value)
    # nothing counted, or a program that keeps no such counter: no value
    empty = {k: 0 for k in WINDOW if k != "gc_pause_s"}
    assert breakdown.COUNTERS[metric](empty) is None
    assert breakdown.COUNTERS[metric]({}) is None


def test_counters_are_window_deltas_and_skip_what_was_not_kept():
    before = {"tier_requests": 100, "tier_batches": 10, "tier_queue_wait_s": 0.2,
              "tier_flush_s": None}
    after = {"tier_requests": 500, "tier_batches": 35, "tier_queue_wait_s": 1.0,
             "tier_flush_s": None}
    got = breakdown.counter_metrics(breakdown.delta(after, before))
    assert got == pytest.approx({"tier_queue_ms.stream": 2.0})


def test_chunks_sum_feed_wait_and_dispatch():
    class Res:
        def __init__(self, w, d):
            self.wait_s, self.dispatch_s = w, d

    closed = []

    def gen():
        try:
            yield Res(0.1, 0.2)
            yield Res(0.3, 0.4)
        finally:
            closed.append(True)

    c = breakdown.Chunks(gen())
    next(c), next(c)
    assert (c.chunks, c.wait_s, c.dispatch_s) == (2, pytest.approx(0.4), pytest.approx(0.6))
    c.close()
    assert closed == [True]


@pytest.mark.parametrize("cell, counted", [
    ("jsc-stream", {"tier_queue_ms.stream", "tier_flush_ms.stream", "gc_pause_ms"}),
    ("jsc-train", {"train_feed_wait_ms", "train_dispatch_ms", "gc_pause_ms"}),
    ("pid-bulk", {"engine_place_ms.bulk", "gc_pause_ms"}),
])
def test_breakdown_of_a_tiny_cell_reads_the_program(cell, counted, tmp_path,
                                                    monkeypatch):
    import time

    import jax

    run = harness.Run(harness.load_bench(), cell, 2 ** 33 + 5, 1.0, True,
                      overrides=cells.TINY[cell])
    run.devices, run.chips = jax.devices(), 1
    peaks = work.peaks
    monkeypatch.setattr(work, "peaks", lambda kind: peaks("TPU v5 lite"))
    out = breakdown.execute(run, time.monotonic(), str(tmp_path / "trace"))
    assert out["correct"], out["checks"]
    assert set(out["counters"]) == counted
    assert all(v >= 0 for v in out["counters"].values())
    assert out["e2e"] and out["n_spans"] > 1     # the window and the program's
    assert not os.path.exists(tmp_path / "trace")
