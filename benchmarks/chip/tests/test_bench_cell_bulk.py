"""pid-bulk at a tiny size on the CPU, and pid-bulk-4chip on four virtual
CPU devices: correct as they stand, not correct with an answer altered or
one device's shard of the output lost."""

import json
import os
import subprocess
import sys

import cells
import faults

HERE = os.path.dirname(os.path.abspath(__file__))


def test_bulk_cell_runs_and_is_correct():
    res = cells.run_cell("pid-bulk")
    assert res["correct"], res["checks"]
    assert res["metrics"]["serve_rows_per_s"]["value"] > 0


def test_altered_answers_are_caught(monkeypatch):
    faults.altered_answers(monkeypatch)
    res = cells.run_cell("pid-bulk", seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["wrong_rows"]["value"] > 0
    assert res["checks"]["inconsistent_rows"]["value"] == 0


SCRIPT = r"""
import json, sys
sys.path[:0] = [%r, %r, %r]
import cells
import faults
fault = sys.argv[1]
if fault == "lost_shard":
    import faults, pytest
    faults.lost_shard(pytest.MonkeyPatch(), 4)
res = cells.run_cell("pid-bulk-4chip", seconds=0.5, chips=4)
print(json.dumps(res))
""" % (HERE, os.path.dirname(HERE),
       os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(HERE))), "src"))


def _run4(fault: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", SCRIPT, fault], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_four_device_cell_is_correct_and_catches_a_lost_shard():
    res = _run4("none")
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    res = _run4("lost_shard")
    assert not res["correct"]
    assert res["checks"]["wrong_rows"]["value"] > 0
