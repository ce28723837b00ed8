"""plf-bulk at a tiny size on the CPU (8 particles of 16 features, 32
events a call): correct as it stands; not correct with an answer altered
where the engine produces it, nor with the plain reference computed in
bfloat16 in the engine's place."""

import time

import jax
import ml_dtypes

import faults
import harness
import work

CELL = "plf-bulk"
TINY = {"config": {"n_particles": 8},
        "traffic": {"rows_per_device": 32, "check_rows_per_device": 8}}
SEED = 2 ** 34 + 17


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


def test_plf_bulk_runs_and_is_correct():
    res = _run()
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "serve_rows_per_s"}
    assert res["metrics"]["serve_rows_per_s"]["value"] > 0


def test_plf_bulk_traced_reads_its_bulk_metrics():
    res = _run(trace=True)
    assert res["correct"], res["checks"]
    assert {"engine_call_ms.bulk", "fetch_ms.bulk",
            "serve_mfu.bulk"} <= set(res["metrics"])


def test_plf_reference_admits_what_the_program_computes():
    """The cell's float64 reference holds the lowered program's own codes
    (``DaisProgram.run``) inside its admitted ranges."""
    run = harness.Run(harness.load_bench(), CELL, SEED, 1.0, False,
                      overrides=TINY)
    params = run.model.make_weights(run.cfg, SEED, serve=True)
    prog = run.model.lower(run.cfg, params)
    codes = run.model.request_codes(run.cfg, SEED, 48)
    assert codes.shape == (48, 8 * 16)
    want = prog.run(codes)
    lo, hi = run.model.Reference(run.cfg, params)(codes)
    assert ((lo <= want) & (want <= hi)).all()
    assert (lo == hi).mean() > 0.99


def test_plf_altered_answers_are_caught(monkeypatch):
    faults.altered_answers(monkeypatch, slice(0, None, 5))
    res = _run(seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["wrong_rows"]["value"] > 0


def test_plf_control_in_the_engines_place_is_caught(monkeypatch):
    """The control: the plain reference in bfloat16 serves in the engine's
    place; the comparison with the float64 reference must fail it."""
    ref = {}

    def control(codes):
        if "r" not in ref:
            run = harness.Run(harness.load_bench(), CELL, SEED, 1.0, False,
                              overrides=TINY)
            p = run.model.make_weights(run.cfg, SEED, serve=True)
            ref["r"] = run.model.Reference(run.cfg, p, dtype=ml_dtypes.bfloat16)
        return ref["r"](codes)

    faults.control_engine(monkeypatch, control)
    res = _run(seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["wrong_rows"]["value"] > 0
