"""jsc-stream at a tiny size on the CPU: correct as it stands, not correct
with an answer altered where the engine produces it."""

import cells
import faults


def test_stream_cell_runs_and_is_correct():
    res = cells.run_cell("jsc-stream")
    assert res["correct"], res["checks"]
    assert res["attempted"] == 200 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "serve_p50_ms"}
    assert res["metrics"]["serve_p50_ms"]["value"] > 0


def test_stream_cell_traced_reads_its_host_metrics():
    res = cells.run_cell("jsc-stream", trace=True)
    m = res["metrics"]
    assert m["tier_batch_fill.stream"]["value"] >= 1
    assert "gen_late_p99_ms.stream" in m
    assert set(res["device"]) >= {"busy_s", "window_s"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_altered_answer_is_caught(monkeypatch):
    faults.altered_answers(monkeypatch, slice(0, 1))
    res = cells.run_cell("jsc-stream")
    assert not res["correct"]
    assert res["checks"]["wrong_rows"]["value"] > 0


def test_control_in_the_engines_place_is_caught(monkeypatch):
    """The control: the plain reference in bfloat16 serves in the engine's
    place; the comparison with the float64 reference must fail it."""
    import ml_dtypes

    import weights

    ref = {}
    def control(codes):
        if "r" not in ref:
            run = cells.harness.Run(cells.harness.load_bench(), "jsc-stream",
                                    0, 1.0, False)
            p = run.model.make_weights(run.cfg, SEED, serve=True)
            ref["r"] = run.model.Reference(run.cfg, weights.to_host(p),
                                           dtype=ml_dtypes.bfloat16)
        return ref["r"](codes)

    faults.control_engine(monkeypatch, control)
    res = cells.run_cell("jsc-stream", seed=SEED)
    assert not res["correct"]
    assert res["checks"]["wrong_rows"]["value"] > res["attempted"] // 2


SEED = 2 ** 35 + 9
