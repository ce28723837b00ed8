"""The trace reducer: busy time as a union of intervals, top ops, gaps."""

import json
import os

import pytest

from harness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
trace = load_module(os.path.join(os.path.dirname(HERE), "trace.py"), "bench_trace")

# a small recorded window: two devices, overlapping ops on the first, and
# the benchmark's host spans (seconds on the profiler's clock)
RECORDED = {
    "devices": {
        "/device:TPU:0": [[1.0, 1.4, "fusion.1"], [1.2, 1.6, "fusion.2"],
                          [2.0, 2.5, "fusion.1"], [0.5, 1.1, "copy"]],
        "/device:TPU:1": [[1.0, 2.0, "fusion.1"]],
    },
    "host": [[1.0, 3.0, "bench.window"], [1.0, 1.5, "bench.engine_call"],
             [1.5, 2.9, "bench.fetch"], [0.0, 0.9, "bench.setup"]],
}


def _load():
    devs = {k: [tuple(e) for e in v] for k, v in RECORDED["devices"].items()}
    return devs, [tuple(e) for e in RECORDED["host"]]


def test_union_merges_overlaps_and_clips_to_window():
    ops = _load()[0]["/device:TPU:0"]
    assert trace.union(ops, 1.0, 3.0) == [(1.0, 1.6), (2.0, 2.5)]
    assert trace.gaps([(1.0, 1.6), (2.0, 2.5)], 1.0, 3.0) == [(1.6, 2.0), (2.5, 3.0)]


def test_reduce_busy_top_ops_and_gaps():
    out = trace.reduce(*_load())
    assert out["window_s"] == pytest.approx(2.0)
    assert out["per_device_busy_s"]["/device:TPU:0"] == pytest.approx(1.1)
    assert out["per_device_busy_s"]["/device:TPU:1"] == pytest.approx(1.0)
    assert out["busy_s"] == pytest.approx(1.05)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["fusion.1"] == pytest.approx(0.4 + 0.5 + 1.0)
    assert ops["copy"] == pytest.approx(0.1)          # clipped at the window
    assert list(ops) == ["fusion.1", "fusion.2", "copy"]
    gaps = dict(out["breakdown"]["idle_gaps"])
    # device 0 idles 0.4 s in the fetch and 0.5 s at the end (also fetch,
    # up to 2.9 s, so the middle of [2.5, 3.0] falls in it); device 1 idles
    # 1.0 s from 2.0, in the fetch; averaged over the two devices
    assert gaps["bench.fetch"] == pytest.approx((0.4 + 0.5 + 1.0) / 2)
    json.dumps(out)


def test_reduce_without_window_span_is_an_error():
    devs, host = _load()
    with pytest.raises(ValueError):
        trace.reduce(devs, [h for h in host if h[2] != "bench.window"])


def test_load_reads_host_spans_of_a_recorded_profile(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x) * 2)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    devs, host = trace.load(str(tmp_path), "/device:TPU:", 1)
    names = [h[2] for h in host]
    assert "bench.window" in names and "bench.call" in names
    assert devs == {}                    # no TPU plane on the CPU
    out = trace.reduce({"/device:TPU:0": []}, host)
    assert out["busy_s"] == 0.0 and out["window_s"] > 0
