"""``chip_smoke.py``'s phases at tiny sizes on the CPU, and its refusal to
run (or to print a result) without a TPU."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402

TINY = dict(batch=64, steps=2, chunk_steps=1, dims=(16, 6, 5), hidden=4)
ENGINES = [(None, "fused"), ("pallas", "pallas")]


@pytest.fixture(scope="module")
def trained():
    return {fused: cs.train_phase(fused=fused, **TINY)
            for fused in (False, True)}


def test_train_paths_agree(trained):
    einsum, fused = trained[False], trained[True]
    assert einsum["fused_layers"] == []
    assert fused["fused_layers"] == [1]    # layer 0 has BN: einsum in train
    assert len(einsum["losses"]) == TINY["steps"]
    assert cs.compare_losses(einsum, fused) <= cs.LOSS_ATOL


def test_compare_losses_rejects_disagreement():
    with pytest.raises(RuntimeError, match="first-step loss"):
        cs.compare_losses({"losses": [1.0]}, {"losses": [1.1]})


@pytest.mark.parametrize("engine,require", ENGINES)
def test_trained_jsc_serves_bit_exact(trained, engine, require):
    prog = cs.lower_jsc(trained[False]["layers"], trained[False]["params"])
    built = cs.build_phase("jsc", prog, engine=engine, require=require)
    assert built.engine.path == require
    assert cs.tier_phase("jsc", built, n_requests=48) == 48


@pytest.mark.parametrize("engine,require", ENGINES)
def test_pid_hybrid_serves_bit_exact(engine, require):
    prog = cs.lower_pid(ctx=40, hidden=4)
    built = cs.build_phase("pid", prog, engine=engine, require=require)
    assert built.engine.path == require
    assert cs.tier_phase("pid", built, n_requests=16) == 16


def test_mesh_phase_on_four_virtual_devices():
    """The --chips 4 path on four CPU devices: sharded, bit-exact."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import chip_smoke as cs
from repro.launch.mesh import make_local_mesh
mesh = make_local_mesh()
layers = cs.jsc_layers((16, 6, 5), 4)
keys = jax.random.split(jax.random.PRNGKey(0), len(layers))
prog = cs.lower_jsc(layers, [l.init(k) for l, k in zip(layers, keys)])
for engine, require in %r:
    built = cs.mesh_phase("jsc", prog, mesh, engine=engine,
                          require=require, batch=64)
    assert cs.tier_phase("jsc", built, n_requests=24, n_replicas=4) == 24
print("MESH_OK")
""" % (ENGINES,)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([REPO, os.path.join(REPO, "src")]))
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert "MESH_OK" in r.stdout, r.stderr[-3000:]
    assert r.stdout.count("over 4 devices, bit-exact True") == 2


def test_main_refuses_without_tpu(capsys):
    assert cs.main([]) != 0
    out = capsys.readouterr().out
    for line in out.splitlines():
        try:
            assert "ok" not in json.loads(line)
        except ValueError:
            pass
