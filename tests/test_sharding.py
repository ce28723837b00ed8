"""Sharding-rule unit tests + miniature (8-device) dry-run in a subprocess."""

import os
import subprocess
import sys

import pytest

from repro.nn.params import PDef
from repro.parallel.sharding import spec_for

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src")}
ENV.pop("XLA_FLAGS", None)

AXES = {"data": 16, "model": 16}
AXES_POD = {"pod": 2, "data": 16, "model": 16}


def P(*args):
    from jax.sharding import PartitionSpec
    return PartitionSpec(*args)


def test_tp_rules():
    d = PDef((16, 2048, 16, 128), ("layers", "embed", "heads", None))
    assert spec_for(d, AXES, fsdp=False) == P(None, None, "model", None)
    v = PDef((50304, 2048), ("vocab", "embed"))
    assert spec_for(v, AXES, fsdp=False) == P("model", None)


def test_kv_heads_fall_back_to_replicated():
    d = PDef((40, 5120, 8, 128), ("layers", "embed", "kv_heads", None))
    # 8 kv heads don't divide model=16 -> replicated
    assert spec_for(d, AXES, fsdp=False) == P(None, None, None, None)
    d2 = PDef((16, 2048, 16, 128), ("layers", "embed", "kv_heads", None))
    assert spec_for(d2, AXES, fsdp=False) == P(None, None, "model", None)


def test_fsdp_shards_embed_over_data():
    d = PDef((35, 7168, 4864), ("layers", "embed", "ffn"))
    assert spec_for(d, AXES, fsdp=True) == P(None, "data", "model")
    # without fsdp: embed replicated
    assert spec_for(d, AXES, fsdp=False) == P(None, None, "model")


def test_ep_experts_then_ffn_overflow():
    d = PDef((35, 128, 7168, 4864), ("layers", "experts", "embed", "ffn"))
    s = spec_for(d, AXES_POD, fsdp=True)
    # experts->model (EP), embed->data (ZeRO), ffn->pod (overflow)
    assert s == P(None, "model", "data", "pod")


def test_no_duplicate_mesh_axis_within_tensor():
    d = PDef((64, 64), ("heads", "kv_heads"))
    s = spec_for(d, AXES, fsdp=False)
    used = [a for a in s if a is not None]
    assert len(used) == len(set(used))


def test_batch_multi_axis():
    d = PDef((256, 4096), ("batch", None))
    s = spec_for(d, AXES_POD, fsdp=False)
    assert s == P(("pod", "data"), None)
    tiny = PDef((1, 4096), ("batch", None))
    assert spec_for(tiny, AXES_POD, fsdp=False) == P(None, None)


def test_kv_seq_takes_model_when_heads_cant():
    d = PDef((40, 128, 8, 32768, 128),
             ("layers", "batch", "kv_heads", "kv_seq", None))
    s = spec_for(d, AXES, fsdp=False)
    assert s == P(None, "data", None, "model", None)   # SP fallback


@pytest.mark.slow
def test_mini_dryrun_8_devices():
    """The full dry-run path on a small forced-device-count mesh."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
import jax.numpy as jnp
from repro.configs.base import get_smoke
from repro.models.registry import build_model
from repro.nn.params import param_shapes
from repro.train import steps as steps_mod
from repro.optim.adam import adam_init
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 4), ("data", "model"))
for arch in ("olmo_1b", "phi35_moe", "rwkv6_16b"):
    cfg = get_smoke(arch)
    model = build_model(cfg, mesh)
    p_shapes = param_shapes(model.defs())
    bs = steps_mod.batch_shardings(model, 32, 4, "train", mesh)
    step_fn, _ = steps_mod.make_train_step(model, mesh, donate=False,
                                           batch_shards=bs)
    o_shapes = jax.eval_shape(adam_init, p_shapes)
    ins = model.input_specs(32, 4, "train")
    compiled = step_fn.lower(p_shapes, o_shapes, ins).compile()
    assert compiled.cost_analysis() is not None
    print("MINI_DRYRUN_OK", arch)
"""
    r = subprocess.run([sys.executable, "-c", code], env=ENV, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.stdout.count("MINI_DRYRUN_OK") == 3, (r.stdout, r.stderr[-3000:])
