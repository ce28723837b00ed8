"""Compile-only rehearsal of the main-path kernels for a TPU v5e.

The TPU compiler is installed even where no chip is attached: these tests
compile, at the sizes the chip runs, the serve mega-kernel (JSC 16->20->5
and the PID hybrid at ctx=100, batch 1024), the fused LUT-Dense forward
and backward at the paper's JSC batch, and both serve engines on a
four-device mesh.  What Mosaic or XLA refuses here (an unsupported op, a
kernel over its VMEM, a call that cannot be partitioned) would fail the
chip run.  Nothing runs, so nothing is timed.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

B_TRAIN, C_IN, HIDDEN, C_OUT = 16600, 16, 8, 20
B_SERVE = 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip; keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        described = topologies.get_topology_desc(platform="tpu",
                                                 topology_name="v5e:2x2")
    except Exception as e:
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield described
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from repro.launch.mesh import make_mesh
    return make_mesh((4,), ("data",), devices=topo.devices)


def _jsc_prog():
    from repro.core.dais import compile_sequential
    from repro.core.lut_layers import LUTDense

    dims = (16, 20, 5)
    layers = [LUTDense(ci, co, hidden=HIDDEN, use_batchnorm=(k == 0))
              for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:]))]
    keys = jax.random.split(jax.random.PRNGKey(0), len(layers))
    return compile_sequential(layers, [l.init(k) for l, k in
                                       zip(layers, keys)], 4, 3)


def _pid_prog():
    from repro.core.lower import lower
    from repro.models.pid import (build_pid_graph, build_pid_layers,
                                  init_pid_params)

    layers = build_pid_layers()
    params = init_pid_params(layers, jax.random.PRNGKey(0))
    return lower(build_pid_graph(layers, n_samples=100), [*params, None])


PROGS = {"jsc": _jsc_prog, "pid": _pid_prog}


def _packed(prog):
    from repro.core.analysis import analyze_ranges
    from repro.kernels.lut_serve import compose_fused_stages
    from repro.kernels.lut_serve_pallas import pack_stages

    stages, why = compose_fused_stages(prog, jnp.int32,
                                       ranges=analyze_ranges(prog))
    assert stages is not None, why
    return pack_stages(stages, jnp.int32)


@pytest.mark.parametrize("model", sorted(PROGS))
def test_mega_kernel_compiles(model, one_chip):
    from repro.kernels.lut_serve_pallas import pallas_runner

    packed = _packed(PROGS[model]())
    run = pallas_runner(packed, jnp.int32, interpret=False)
    x = jax.ShapeDtypeStruct((B_SERVE, packed.n_cols0), jnp.int32,
                             sharding=one_chip)
    compiled = jax.jit(run).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_lut_dense_kernels_compile(direction, one_chip):
    from repro.kernels.lut_dense import lut_dense_fused
    from repro.kernels.lut_dense_bwd import lut_dense_bwd_fused

    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    w = f32(C_IN, HIDDEN, C_OUT)
    q = f32(C_IN, C_OUT)
    args = [f32(B_TRAIN, C_IN), w, w, w, q, q, q, q, q]
    if direction == "fwd":
        lowered = jax.jit(lut_dense_fused).lower(*args)
    else:
        lowered = jax.jit(lut_dense_bwd_fused).lower(*args,
                                                     f32(B_TRAIN, C_OUT))
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("engine", ["fused", "pallas"])
def test_serve_engines_partition_over_four_devices(engine, mesh4):
    """Batch-sharded engines: every device runs its own rows, with no
    collective, and the output stays sharded over all four devices."""
    from repro.kernels.lut_serve import compile_program
    from repro.kernels.lut_serve_pallas import pallas_runner

    prog = _jsc_prog()
    if engine == "fused":
        run = compile_program(prog, engine="fused", mesh=mesh4)._runner
    else:
        run = jax.jit(pallas_runner(_packed(prog), jnp.int32, mesh4,
                                    interpret=False))
    x = jax.ShapeDtypeStruct((B_SERVE, len(prog.input_f)), jnp.int32,
                             sharding=NamedSharding(mesh4, P("data", None)))
    compiled = run.lower(x).compile()
    text = compiled.as_text()
    assert not any(op in text for op in ("all-gather", "all-reduce",
                                         "all-to-all", "collective-permute"))
    assert len(compiled.output_shardings.device_set) == 4
    if engine == "pallas":
        assert "tpu_custom_call" in text
