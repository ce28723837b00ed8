"""Checkpointing + fault-tolerance tests: atomic, async, resume, elastic."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.store import CheckpointStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
ENV.pop("XLA_FLAGS", None)


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "nested": {"b": jnp.arange(5, dtype=jnp.int32)}}


def test_roundtrip(tmp_path):
    store = CheckpointStore(str(tmp_path))
    params = _tree()
    opt = {"m": jax.tree.map(jnp.zeros_like, params), "step": jnp.asarray(3)}
    store.save(3, params, opt, extra={"cursor": 42}, blocking=True)
    p2, o2, man = store.restore(params, opt)
    assert man["step"] == 3 and man["cursor"] == 42
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_retention_gc(tmp_path):
    store = CheckpointStore(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        store.save(s, _tree(), blocking=True)
    assert store.list_steps() == [3, 4]


def test_restore_rejects_shape_mismatch(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save(1, _tree(), blocking=True)
    bad = {"a": jnp.zeros((5, 5)), "nested": {"b": jnp.zeros(5, jnp.int32)}}
    with pytest.raises(ValueError):
        store.restore(bad)


def test_atomicity_no_tmp_left(tmp_path):
    store = CheckpointStore(str(tmp_path))
    store.save(7, _tree(), blocking=True)
    files = os.listdir(tmp_path)
    assert not any(f.endswith(".tmp") for f in files)
    assert "step_0000000007.npz" in files


def test_crash_resume_bit_identical(tmp_path):
    """Train 12 steps with a crash at 6 + resume == train 12 straight.

    Proves: atomic checkpoints, deterministic data cursor, exact resume.
    """
    ckpt_a = str(tmp_path / "a")
    ckpt_b = str(tmp_path / "b")
    base = [sys.executable, "-m", "repro.launch.train", "--arch", "olmo_1b",
            "--smoke", "--batch", "2", "--seq", "32", "--ckpt-every", "3",
            "--log-every", "100"]
    # crashing run + resume
    r = subprocess.run(base + ["--steps", "12", "--ckpt-dir", ckpt_a,
                               "--simulate-crash", "6"],
                       env=ENV, cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 17, r.stderr[-2000:]
    r = subprocess.run(base + ["--steps", "12", "--ckpt-dir", ckpt_a],
                       env=ENV, cwd=REPO, capture_output=True, text=True)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "resumed from step 6" in r.stdout
    # straight run
    r2 = subprocess.run(base + ["--steps", "12", "--ckpt-dir", ckpt_b],
                        env=ENV, cwd=REPO, capture_output=True, text=True)
    assert r2.returncode == 0, r2.stderr[-2000:]

    import numpy as np
    za = np.load(os.path.join(ckpt_a, "step_0000000012.npz"))
    zb = np.load(os.path.join(ckpt_b, "step_0000000012.npz"))
    assert sorted(za.files) == sorted(zb.files)
    for k in za.files:
        np.testing.assert_array_equal(za[k], zb[k], err_msg=k)


def test_elastic_restore_different_mesh(tmp_path):
    """Save on 1 device, restore + reshard onto an 8-device mesh (subprocess)."""
    store = CheckpointStore(str(tmp_path))
    store.save(1, _tree(), blocking=True)
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.ckpt.store import CheckpointStore
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
ref = {{"a": jnp.zeros((4, 8)), "nested": {{"b": jnp.zeros(5, jnp.int32)}}}}
sh = {{"a": NamedSharding(mesh, P("data", "model")),
      "nested": {{"b": NamedSharding(mesh, P())}}}}
p, _, man = CheckpointStore({str(tmp_path)!r}).restore(ref, shardings=sh)
assert p["a"].sharding.spec == P("data", "model"), p["a"].sharding
assert len(p["a"].devices()) == 8
print("ELASTIC_OK")
"""
    r = subprocess.run([sys.executable, "-c", code], env=ENV, cwd=REPO,
                       capture_output=True, text=True)
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]
