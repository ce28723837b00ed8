"""Launcher smoke tests: serve loop + straggler watchdog run end-to-end."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
ENV.pop("XLA_FLAGS", None)


@pytest.mark.slow
def test_serve_launcher_decodes():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen15_05b",
         "--smoke", "--batch", "2", "--prompt-len", "16", "--gen", "8"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "decode=" in r.stdout and "sample generations" in r.stdout


@pytest.mark.slow
def test_serve_launcher_tables_engine():
    """--engine tables: compiled integer artifact serves, gate passes."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--engine", "tables",
         "--lut-dims", "8,6,3", "--lut-hidden", "4", "--batch", "256",
         "--gen", "2", "--smoke"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "engine=tables" in r.stdout
    assert "bit-exact gate PASSED" in r.stdout
    assert "rows/s" in r.stdout


@pytest.mark.slow
def test_serve_launcher_artifact_cache_and_loop(tmp_path):
    """Cold start from a saved bundle: second invocation skips lowering AND
    (with --skip-verify-cached) the gate, then serves the async loop with
    p50/p99 + throughput reporting."""
    bundle = str(tmp_path / "model.npz")
    common = [sys.executable, "-m", "repro.launch.serve", "--engine", "tables",
              "--lut-dims", "8,6,3", "--lut-hidden", "4", "--smoke",
              "--artifact", bundle]
    r1 = subprocess.run(common + ["--batch", "64", "--gen", "1"],
                        env=ENV, cwd=REPO, capture_output=True, text=True,
                        timeout=600)
    assert r1.returncode == 0, r1.stderr[-2000:]
    assert "bit-exact gate PASSED" in r1.stdout
    assert "artifact saved" in r1.stdout
    assert os.path.exists(bundle)

    r2 = subprocess.run(common + ["--skip-verify-cached", "--serve-loop",
                                  "--rate", "0", "--requests", "96",
                                  "--max-batch", "16"],
                        env=ENV, cwd=REPO, capture_output=True, text=True,
                        timeout=600)
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "artifact loaded" in r2.stdout
    assert "no re-lowering" in r2.stdout
    assert "gate SKIPPED: cached attestation" in r2.stdout
    for token in ("p50=", "p99=", "throughput=", "bit-exact vs"):
        assert token in r2.stdout, r2.stdout

    # tampered bundle must be refused outright
    import numpy as np
    with np.load(bundle) as z:
        arrays = {k: z[k].copy() for k in z.files}
    key = next(k for k in arrays if k.startswith("fused/")
               and k.endswith("_table"))
    arrays[key][0, 0, 0] ^= 1
    np.savez(bundle, **arrays)
    r3 = subprocess.run(common + ["--skip-verify-cached", "--batch", "16",
                                  "--gen", "1"],
                        env=ENV, cwd=REPO, capture_output=True, text=True,
                        timeout=600)
    assert r3.returncode != 0
    assert "hash mismatch" in (r3.stderr + r3.stdout)


def test_serve_launcher_tier_reports_queue_flush_and_collector():
    """--replicas 2: the tier's summary reads its counters and the
    collector hook the launcher installs."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--engine", "tables",
         "--lut-dims", "8,6,3", "--lut-hidden", "4", "--smoke", "--serve-loop",
         "--replicas", "2", "--rate", "0", "--requests", "64",
         "--max-batch", "16"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = next(x for x in r.stdout.splitlines() if "mean queue wait=" in x)
    assert "mean flush=" in line and "collector pauses=" in line


@pytest.mark.slow
def test_serve_launcher_pid_hybrid():
    """--model pid-hybrid: the hybrid conv program compiles through the
    graph frontend, serves on the fused shared-table path, gate passes."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--engine", "tables",
         "--model", "pid-hybrid", "--ctx", "60", "--smoke",
         "--batch", "32", "--gen", "1"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "model=pid-hybrid" in r.stdout
    assert "path=fused" in r.stdout
    assert "bit-exact gate PASSED" in r.stdout


@pytest.mark.slow
def test_train_launcher_smoke():
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "rwkv6_16b",
         "--smoke", "--steps", "4", "--batch", "2", "--seq", "32",
         "--log-every", "2"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: 4 steps" in r.stdout
    assert "collector pauses" in r.stdout    # the hook the launcher installs


@pytest.mark.slow
def test_train_launcher_chunked_flags_smoke():
    """--chunk-steps/--no-prefetch: explicit chunking flags drive the same
    loop; a chunk size that doesn't divide --steps still runs every step."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "rwkv6_16b",
         "--smoke", "--steps", "5", "--batch", "2", "--seq", "32",
         "--log-every", "2", "--chunk-steps", "3", "--no-prefetch"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: 5 steps" in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "rwkv6_16b",
         "--smoke", "--steps", "1", "--chunk-steps", "0"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode != 0
    assert "chunk-steps" in r.stderr


def test_train_launcher_rejects_zero_beta_final():
    """Regression: `--beta-final 0.0` used to silently mean "constant β"
    (falsy-zero flag handling); it must now be an explicit error."""
    from repro.launch.train import main
    with pytest.raises(SystemExit, match="beta-final"):
        main(["--arch", "olmo_1b", "--smoke", "--steps", "1",
              "--beta-final", "0.0"])
    with pytest.raises(SystemExit, match="beta-init"):
        main(["--arch", "olmo_1b", "--smoke", "--steps", "1",
              "--beta-init", "0.0", "--beta-final", "1e-3"])


@pytest.mark.slow
def test_train_launcher_beta_ramp_finite():
    """`--beta-final 1e-3` (the paper ramp, defaulting β₀ to 5e-7) trains
    with finite printed loss — regression for the log(0) NaN ramp."""
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "olmo_1b",
         "--smoke", "--steps", "4", "--batch", "2", "--seq", "32",
         "--log-every", "1", "--beta-final", "1e-3"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "done: 4 steps" in r.stdout
    assert "nan" not in r.stdout.lower(), r.stdout


@pytest.mark.slow
def test_pareto_launcher_smoke(tmp_path):
    """The β-sweep Pareto launcher: one ramped run, ≥3 operating points
    with accuracy/EBOPs/LUT/latency fields, a selected point served
    through the artifact + scheduler path, and a JSON report."""
    import json
    out = str(tmp_path / "pareto.json")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.pareto", "--smoke",
         "--out", out, "--ckpt-dir", str(tmp_path / "ckpt"),
         "--serve-requests", "48"],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "frontier" in r.stdout and "served" in r.stdout
    with open(out) as fh:
        payload = json.load(fh)
    points = payload["points"]
    assert len(points) >= 3
    for p in points:
        for key in ("beta", "val_acc", "test_acc", "ebops", "est_luts",
                    "n_llut", "n_llut_live", "gather_width",
                    "gather_width_dce", "engine_us", "rows_per_s"):
            assert key in p, key
        assert p["verify"]["random"] > 0          # every point was gated
    assert payload["serve"]["engine"]["p50_ms"] > 0
    assert os.path.exists(payload["serve"]["bundle"])
