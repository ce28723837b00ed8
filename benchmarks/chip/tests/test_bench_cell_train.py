"""jsc-train at a tiny size on the CPU: correct as it stands, not correct
with the train step broken underneath."""

import numpy as np

import cells
import faults


def test_train_cell_runs_and_is_correct():
    res = cells.run_cell("jsc-train")
    assert res["correct"], res["checks"]
    assert res["metrics"]["train_samples_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_state_left_unchanged_is_caught(monkeypatch):
    faults.stuck_state(monkeypatch)
    res = cells.run_cell("jsc-train")
    assert not res["correct"]
    assert res["checks"]["update_gap"]["value"] > res["checks"]["update_gap"]["limit"]


def test_half_batch_is_caught(monkeypatch):
    faults.half_batch(monkeypatch)
    res = cells.run_cell("jsc-train")
    assert not res["correct"]
    c = res["checks"]
    assert c["loss_gap"]["value"] > c["loss_gap"]["limit"]
    assert np.isfinite(c["grad_gap"]["value"])


def test_control_in_the_programs_place_is_caught():
    """The control: the plain reference computed in bfloat16 in the
    program's place fails at least one number against float32."""
    import control
    import harness

    run = harness.Run(harness.load_bench(), "jsc-train", 0, 1.0, False,
                      overrides={"traffic": {"batch": 512, "dataset_rows": 2048}})
    out = control.train_control(harness, run, 2 ** 36 + 3)
    assert not out["correct"]
    assert out["checks"]["loss_gap"] > run.limits["loss_gap"]
