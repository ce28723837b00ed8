"""pid-stream at a tiny size on the CPU (200-sample waveforms, 60
requests a second): correct as it stands, not correct with an answer
altered where the engine produces it."""

import time

import jax

import faults
import harness
import work

CELL = "pid-stream"
TINY = {"config": {"n_samples": 200},
        "traffic": {"rate_per_s": 60, "warm_s": 0.1, "max_batch": 16}}
SEED = 2 ** 33 + 29


def _run(seed: int = SEED, seconds: float = 1.0, trace: bool = False) -> dict:
    run = harness.Run(harness.load_bench(), CELL, seed, seconds, trace,
                      overrides=TINY)
    run.devices = jax.devices()
    peaks = work.peaks
    work.peaks = lambda kind: peaks("TPU v5 lite")
    try:
        return harness.execute(run, time.monotonic())
    finally:
        work.peaks = peaks


def test_pid_stream_runs_and_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert res["attempted"] == 60 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "serve_p50_ms"}
    assert res["metrics"]["serve_p50_ms"]["value"] > 0


def test_pid_stream_traced_reads_its_stream_metrics():
    res = _run(trace=True)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    assert m["tier_batch_fill.stream"]["value"] >= 1
    assert "gen_late_p99_ms.stream" in m


def test_pid_stream_altered_answer_is_caught(monkeypatch):
    faults.altered_answers(monkeypatch, slice(0, 1))
    res = _run()
    assert not res["correct"]
    assert res["checks"]["wrong_rows"]["value"] > 0


def test_pid_stream_control_in_the_engines_place_is_caught(monkeypatch):
    """The control: the PID model's plain reference in bfloat16 serves in
    the engine's place; the comparison with the float64 reference must
    fail it."""
    import ml_dtypes

    ref = {}

    def control(codes):
        if "r" not in ref:
            run = harness.Run(harness.load_bench(), CELL, SEED, 1.0, False,
                              overrides=TINY)
            p = run.model.make_weights(run.cfg, SEED, serve=True)
            ref["r"] = run.model.Reference(run.cfg, p, dtype=ml_dtypes.bfloat16)
        return ref["r"](codes)

    faults.control_engine(monkeypatch, control)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["wrong_rows"]["value"] > 0
