"""Accelerator serving engine: bit-exactness vs the DAIS interpreter.

The contract under test (ISSUE 2 acceptance): the jitted integer engine of
``kernels/lut_serve.py`` must match ``DaisProgram.run`` code-for-code — on
exhaustive small-width inputs, on random inputs, on both lowering paths
(fused per-layer tables and generic op groups), and through the sharded
serving entry.  ``LayerTables.lookup_codes`` is pulled into the same
equality for single-layer programs, closing the triangle between the three
implementations of the WRAP indexing contract.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dais import compile_sequential
from repro.core.hgq_layers import HGQDense
from repro.core.lut_layers import LUTDense
from repro.core.quant import QuantConfig, quantize_to_int
from repro.core.tables import extract_tables
from repro.kernels.lut_serve import (EnginePathWarning, _requant_cols,
                                     compile_program, input_code_bounds,
                                     lower_tables, verify_engine)

from _hgq_progs import mixed_linear_prog, nonlinear_prog, per_cell_requant_prog

KEY = jax.random.PRNGKey(11)
IN_F, IN_I = 4, 2


def _narrow_cfg(overflow):
    # clamp widths so an exhaustive sweep over all input codes stays tiny
    return QuantConfig(granularity="element", signed=True, overflow=overflow,
                       init_f=1.0, init_i=1.0, min_f=-2, max_f=2,
                       min_i=-2, max_i=2)


def _codes(n, ci, key=KEY, f=IN_F, i=IN_I):
    x = np.asarray(jax.random.normal(key, (n, ci))) * 2
    return quantize_to_int(x, f, i, True, "SAT")


# --------------------------------------------------------------------------- #
# exhaustive: interpreter == lookup_codes == jitted engine, all input codes
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("fuse", [True, False])
def test_exhaustive_three_way_bit_exact(fuse):
    layer = LUTDense(3, 4, hidden=4,
                     q_in=_narrow_cfg("WRAP"), q_out=_narrow_cfg("SAT"))
    params = layer.init(jax.random.PRNGKey(7))
    in_f = in_i = 1                       # 3-bit inputs -> 8**3 = 512 rows
    prog = compile_sequential([layer], [params], in_f, in_i)
    engine = compile_program(prog, fuse_layers=fuse)
    assert engine.fused is fuse

    lo, hi = input_code_bounds(prog)
    grids = np.meshgrid(*[np.arange(l, h + 1) for l, h in zip(lo, hi)],
                        indexing="ij")
    codes = np.stack([g.ravel() for g in grids], axis=-1)       # (512, 3)
    assert codes.shape[0] == 512

    ref = prog.run(codes)
    got = np.asarray(jax.device_get(engine.run(codes)), np.int64)
    np.testing.assert_array_equal(got, ref)

    t = prog.tables[0]
    np.testing.assert_array_equal(t.lookup_codes(codes, in_f), ref)

    # the packaged gate agrees (and actually runs the exhaustive sweep)
    stats = verify_engine(engine, prog, n_random=64, exhaustive_limit=1024)
    assert stats["exhaustive"] == 512


# --------------------------------------------------------------------------- #
# random, multi-layer, both lowering paths
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("fuse", [True, False])
def test_two_layer_random_bit_exact(fuse):
    l1 = LUTDense(6, 9, hidden=4, use_batchnorm=True)
    l2 = LUTDense(9, 3, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([l1, l2], [l1.init(k1), l2.init(k2)],
                              IN_F, IN_I)
    engine = compile_program(prog, fuse_layers=fuse)
    assert engine.fused is fuse
    codes = _codes(512, 6)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(engine.run(codes)), np.int64),
        prog.run(codes))


def test_hybrid_program_fuses():
    """HGQ segments compose too: enumerated per-cell tables + relu epilogue
    — the fused path now covers hybrid programs instead of falling back."""
    h1 = HGQDense(6, 5, activation="relu")
    l1 = LUTDense(5, 4, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([h1, l1], [h1.init(k1), l1.init(k2)],
                              IN_F, IN_I)
    engine = compile_program(prog)
    assert engine.fused and engine.path == "fused"
    assert engine.fuse_reason == ""
    verify_engine(engine, prog, n_random=512)
    # the generic group path still covers the same program bit-exactly
    generic = compile_program(prog, fuse_layers=False)
    assert generic.path == "generic"
    assert "fuse_layers=False" in generic.fuse_reason
    verify_engine(generic, prog, n_random=512)


def test_hybrid_conv_graph_three_way_bit_exact():
    """The PID shape end-to-end: fused shared-table engine vs generic group
    engine vs numpy interpreter, all code-for-code equal."""
    from repro.core.lower import GraphInput, ModelGraph, WindowSum, lower
    from repro.core.hgq_layers import HGQConv1D
    from repro.core.lut_layers import LUTConv1D

    t_len = 16
    front = HGQConv1D(c_in=1, c_out=3, kernel=4, stride=4, activation="relu")
    lc = LUTConv1D(c_in=3, c_out=3, kernel=3, padding="SAME", hidden=4)
    head = LUTDense(3, 1, hidden=4)
    ks = jax.random.split(KEY, 3)
    params = [front.init(ks[0]), lc.init(ks[1]), head.init(ks[2])]
    graph = ModelGraph(GraphInput((t_len, 1), IN_F, IN_I),
                       [front, lc, head, WindowSum()])
    prog = lower(graph, params + [None])

    fused = compile_program(prog)
    assert fused.path == "fused"
    assert fused.n_groups == 4              # one stage per graph layer
    generic = compile_program(prog, fuse_layers=False)
    assert generic.path == "generic"

    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(5).integers(lo, hi + 1, (256, len(lo)))
    ref = prog.run(codes)
    for eng in (fused, generic):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(eng.run(codes)), np.int64), ref)
        verify_engine(eng, prog, n_random=128)


def test_standalone_relu_wide_operand_fuses_as_epilogue():
    """A standalone ReLU runs its chain as the stage epilogue — table-free,
    so operands wider than the enumeration cap (and with per-channel
    formats) still fuse."""
    from repro.core.lower import GraphInput, ModelGraph, ReLU, lower

    h1 = HGQDense(6, 3)       # no activation: wide mixed-width accumulators
    graph = ModelGraph(GraphInput((6,), IN_F, IN_I), [h1, ReLU()])
    prog = lower(graph, [h1.init(jax.random.PRNGKey(2)), None])
    engine = compile_program(prog)
    assert engine.path == "fused" and engine.n_groups == 2
    verify_engine(engine, prog, n_random=512)


def test_structural_relu_flatten_graph_fuses():
    """Standalone ReLU / Flatten nodes compose too (relu as an enumerated
    stage, flatten as pure column bookkeeping)."""
    from repro.core.lower import Flatten, GraphInput, ModelGraph, ReLU, lower
    from repro.core.lut_layers import LUTConv1D

    conv = LUTConv1D(c_in=2, c_out=3, kernel=2, hidden=4)
    tail = LUTDense(9, 2, hidden=4)
    k1, k2 = jax.random.split(KEY)
    graph = ModelGraph(GraphInput((4, 2), IN_F, IN_I),
                       [conv, ReLU(), Flatten(), tail])
    prog = lower(graph, [conv.init(k1), None, None, tail.init(k2)])
    engine = compile_program(prog)
    assert engine.path == "fused" and engine.n_groups == 3
    verify_engine(engine, prog, n_random=512)


def test_mixed_epilogue_passthrough_channel_not_clamped():
    """A channel with no epilogue instruction must pass through the stage's
    REQUANT epilogue untouched: a fake 'identity' requant would SAT-clamp
    legal unsigned values near the dtype width cap (regression)."""
    from repro.core.dais import DaisProgram, Reg, Segment
    prog = DaisProgram()
    prog.input_f = [0, 0]
    prog.input_signed = [True, False]
    r0 = prog.emit("IN", (0,), Reg(0, 8, True))
    r1 = prog.emit("IN", (1,), Reg(0, 8, False))
    # output A: two-term sum + relu requant (real epilogue)
    a1 = prog.emit("CMUL", (r0, 3, 0), Reg(0, 11, True))
    a2 = prog.emit("CMUL", (r1, 5, 0), Reg(0, 12, True))
    s = prog.emit("ADD", (a1, a2), Reg(0, 13, True))
    out_a = prog.emit("REQUANT", (s, 0, 13, False, "SAT", 0),
                      Reg(0, 13, False))
    # output B: pure univariate chain whose unsigned values reach past
    # 2**29 — above the signed width-30 clamp a fake identity would apply
    out_b = prog.emit("CMUL", (r1, 1 << 22, 0), Reg(0, 30, False))
    prog.outputs = [out_a, out_b]
    prog.output_f = [0, 0]
    prog.segments.append(Segment(kind="hgq", layer_id=0,
                                 in_regs=(r0, r1), out_regs=(out_a, out_b)))
    assert prog.required_width() <= 30          # int32 engine territory
    engine = compile_program(prog)
    assert engine.path == "fused"
    # codes near the top of r1's range drive B beyond 2**29
    codes = np.stack([np.arange(-128, 128), np.arange(256)], axis=-1)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(engine.run(codes)), np.int64),
        prog.run(codes))
    verify_engine(engine, prog, n_random=256)


def test_fuse_fallback_reason_wide_operand():
    """Un-enumerable HGQ operand widths must fall back *loudly*: the reason
    is logged and recorded on the engine, never a silent path switch.  A
    24-bit operand under a non-linear REQUANT → CMUL → REQUANT chain fits
    neither the "mac" form nor an enumerated table."""
    prog = nonlinear_prog(width=24)
    engine = compile_program(prog)
    assert engine.path == "generic" and not engine.fused
    assert "enumerate" in engine.fuse_reason
    assert engine.stage_kinds == ()
    verify_engine(engine, prog, n_random=256)


def test_wide_linear_hgq_runs_as_mac():
    """A 24-bit linear HGQDense enumerates nothing as a "mac" stage, so it
    fuses past the enumeration cap; the Pallas path, which packs the
    enumerated form, degrades to it loudly."""
    h1 = HGQDense(3, 2)
    prog = compile_sequential([h1], [h1.init(KEY)], input_f=18, input_i=6)
    engine = compile_program(prog)
    assert engine.path == "fused" and engine.fuse_reason == ""
    assert engine.stage_kinds == ("mac",)
    verify_engine(engine, prog, n_random=256)
    with pytest.warns(EnginePathWarning, match="enumerate"):
        pallas = compile_program(prog, engine="pallas")
    assert pallas.path == "fused" and pallas.stage_kinds == ("mac",)


def test_hgq_conv_front_composes_to_mac():
    """The PID shape's HGQ conv front (one shared REQUANT per position, one
    CMUL per term, relu) runs as an integer multiply-accumulate, bit-exact
    against the interpreter and the generic engine."""
    from repro.core.hgq_layers import HGQConv1D
    from repro.core.lower import GraphInput, ModelGraph, WindowSum, lower
    from repro.core.lut_layers import LUTConv1D

    front = HGQConv1D(c_in=1, c_out=4, kernel=5, stride=5, activation="relu")
    lc = LUTConv1D(c_in=4, c_out=3, kernel=3, padding="SAME", hidden=4)
    head = LUTDense(3, 1, hidden=4)
    ks = jax.random.split(jax.random.PRNGKey(23), 3)
    params = [front.init(ks[0]), lc.init(ks[1]), head.init(ks[2])]
    # 9-bit inputs (i=4): the front's requant (f=6, i=3) saturates at both
    # ends of the input range
    graph = ModelGraph(GraphInput((20, 1), IN_F, 4),
                       [front, lc, head, WindowSum()])
    prog = lower(graph, params + [None])

    fused = compile_program(prog)
    assert fused.path == "fused" and fused.fuse_reason == ""
    assert fused.stage_kinds == ("mac", "lut", "lut", "sum")
    generic = compile_program(prog, fuse_layers=False)
    # random rows, then rows at both ends of every input's range and
    # alternating between them
    lo, hi = input_code_bounds(prog)
    alt = np.where(np.arange(len(lo)) % 2 == 0, lo, hi)
    codes = np.concatenate([
        np.random.default_rng(9).integers(lo, hi + 1, (256, len(lo))),
        np.stack([lo, hi, alt, lo + hi - alt])])
    ref = prog.run(codes)
    for eng in (fused, generic):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(eng.run(codes)), np.int64), ref)
    verify_engine(fused, prog, n_random=256)


def test_linear_chains_with_bare_positions_run_as_mac():
    """Bare CMUL and bare-register terms next to a shared requant: still a
    "mac" stage (the requant applied where the program has one), exact over
    the whole input space."""
    prog = mixed_linear_prog()
    engine = compile_program(prog)
    assert engine.path == "fused" and engine.stage_kinds == ("mac",)
    stats = verify_engine(engine, prog, n_random=64, exhaustive_limit=1 << 16)
    assert stats["exhaustive"] == 1 << 16


@pytest.mark.parametrize("build", [per_cell_requant_prog, nonlinear_prog],
                         ids=["per_cell_requant", "nonlinear"])
def test_non_mac_chains_stay_enumerated(build):
    """A position requantised differently per output, or a chain with a
    non-linear step, keeps its enumerated "lut" table."""
    prog = build()
    engine = compile_program(prog)
    assert engine.path == "fused" and engine.stage_kinds == ("lut",)
    stats = verify_engine(engine, prog, n_random=64, exhaustive_limit=1 << 16)
    assert stats["exhaustive"] == 1 << 16


@pytest.mark.parametrize("model", ["pid", "jsc", "plf"])
def test_default_engine_stage_kinds(model):
    """With the default EngineSpec the PID hybrid runs its HGQ front as
    "mac" and the rest as before; a LUT-Dense stack runs no "mac" stage;
    JSC-PLF runs its encoder, its set sum (a window sum in the middle of
    the chain) and its head as "lut", "sum", "lut"."""
    from repro.serve.api import EngineSpec, build

    if model == "pid":
        from repro.core.lower import lower
        from repro.models.pid import (build_pid_graph, build_pid_layers,
                                      init_pid_params)
        layers = build_pid_layers()
        params = init_pid_params(layers, jax.random.PRNGKey(0))
        prog = lower(build_pid_graph(layers, n_samples=100),
                     [*params, None])
        want = ("mac", "lut", "lut", "lut", "sum")
    elif model == "plf":
        from repro.core.lower import lower
        from repro.models.plf import (build_plf_graph, build_plf_layers,
                                      init_plf_params)
        layers = build_plf_layers(n_features=4)
        params = init_plf_params(layers, jax.random.PRNGKey(0))
        prog = lower(build_plf_graph(layers, n_particles=8), params)
        want = ("lut", "sum", "lut")
    else:
        layers = [LUTDense(16, 20, hidden=8, use_batchnorm=True),
                  LUTDense(20, 5, hidden=8)]
        keys = jax.random.split(KEY, 2)
        prog = compile_sequential(
            layers, [l.init(k) for l, k in zip(layers, keys)], 4, 3)
        want = ("lut", "lut")
    engine = build(prog, EngineSpec(n_random=256)).engine
    assert engine.path == "fused"
    assert engine.stage_kinds == want


def test_engine_run_float_matches_interpreter():
    layer = LUTDense(4, 3, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)
    engine = compile_program(prog)
    x = np.asarray(jax.random.normal(KEY, (64, 4)), np.float64)
    from repro.core.quant import int_to_float
    xq = int_to_float(quantize_to_int(x, IN_F, IN_I, True, "SAT"), IN_F)
    np.testing.assert_array_equal(engine.run_float(xq), prog.run_float(xq))


def test_engine_with_mesh_sharding_bit_exact():
    """Batch-sharded serving (parallel/sharding.constrain) changes nothing."""
    layer = LUTDense(5, 6, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh()
    engine = compile_program(prog, mesh=mesh)
    verify_engine(engine, prog, n_random=512)


# --------------------------------------------------------------------------- #
# per-layer lowering (LayerTables -> batched gather)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("seed", [0, 1])
def test_lower_tables_matches_lookup_codes(seed):
    k = jax.random.PRNGKey(seed)
    layer = LUTDense(6, 9, hidden=4, use_batchnorm=(seed % 2 == 0))
    t = extract_tables(layer, layer.init(k))
    fn = lower_tables(t, IN_F, x_width=IN_F + IN_I + 1)
    codes = _codes(256, 6, k)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(fn(codes)), np.int64),
        t.lookup_codes(codes, IN_F))


# --------------------------------------------------------------------------- #
# unit: vectorized requant vs the scalar interpreter helper
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("mode", ["SAT", "WRAP"])
def test_requant_cols_matches_scalar_requant(mode):
    from repro.core.dais import _requant
    rng = np.random.default_rng(3)
    n = 32
    src_f = rng.integers(-2, 4, n)
    f = rng.integers(-2, 4, n)          # mixed-sign shifts in one group
    i = rng.integers(0, 4, n)
    v = rng.integers(-200, 200, (17, n))
    ref = np.stack([
        _requant(v[:, c], int(src_f[c]), int(f[c]), int(i[c]), True, mode)
        for c in range(n)], axis=-1)
    got = np.asarray(jax.device_get(_requant_cols(
        jnp.asarray(v, jnp.int32), jnp.asarray(f - src_f, jnp.int32),
        jnp.asarray(f + i + 1, jnp.int32), jnp.asarray(np.ones(n, bool)),
        mode)), np.int64)
    np.testing.assert_array_equal(got, ref)


def test_lookup_codes_tolerates_pruned_cell_with_large_f_out():
    """A dead cell may keep f_out > common_f_out(); its codes are all 0, so
    the alignment shift must clamp instead of going negative (regression:
    numpy raised on integer ** negative)."""
    from repro.core.tables import LayerTables
    g = lambda a: np.asarray(a, np.int32)
    t = LayerTables(
        f_in=g([[1, 1]]), i_in=g([[1, 1]]),
        f_out=g([[1, 7]]), i_out=g([[1, -8]]),
        in_width=g([[3, 0]]), out_width=g([[3, 0]]),
        codes=np.arange(16).reshape(1, 2, 8).astype(np.int64) % 5
              * np.asarray([1, 0])[None, :, None])
    codes = np.arange(-4, 4, dtype=np.int64)[:, None]       # (8, 1) inputs
    out = t.lookup_codes(codes, 1)                           # must not raise
    fn = lower_tables(t, 1, x_width=4)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(fn(codes)), np.int64), out)


def test_required_width_guards_transient_requant_overflow():
    """Declared widths <= 30 but a SAT REQUANT up-shift transient needs more:
    the engine must refuse int32 rather than silently clamp wrong."""
    from repro.core.dais import DaisProgram, Reg
    prog = DaisProgram()
    prog.input_f = [0]
    prog.input_signed = [True]
    r0 = prog.emit("IN", (0,), Reg(f=0, width=29, signed=True))
    r1 = prog.emit("REQUANT", (r0, 6, 23, True, "SAT", 0),
                   Reg(f=6, width=30, signed=True))
    prog.outputs = [r1]
    prog.output_f = [6]
    assert prog.max_width() <= 30 < prog.required_width()
    if jax.config.jax_enable_x64:
        engine = compile_program(prog)
        verify_engine(engine, prog, n_random=128)
    else:
        with pytest.raises(ValueError, match="X64"):
            compile_program(prog)


def test_explicit_dtype_that_overflows_is_rejected():
    """Regression: an *explicit* engine dtype used to skip the width guard.

    Two silent-wrap holes: dtype=int32 on a program whose transients need
    more than 30 bits, and dtype=int64 with JAX_ENABLE_X64 off (jax then
    silently downgrades every array to int32).  Both must raise with an
    actionable message, not serve wrapped values."""
    from repro.core.dais import DaisProgram, Reg
    prog = DaisProgram()
    prog.input_f = [0]
    prog.input_signed = [True]
    r0 = prog.emit("IN", (0,), Reg(f=0, width=29, signed=True))
    r1 = prog.emit("REQUANT", (r0, 6, 23, True, "SAT", 0),
                   Reg(f=6, width=30, signed=True))
    prog.outputs = [r1]
    prog.output_f = [6]
    assert prog.required_width() > 30

    with pytest.raises(ValueError, match="overflow-wrap"):
        compile_program(prog, dtype=jnp.int32)
    if not jax.config.jax_enable_x64:
        # the sneaky case: int64 was *requested* but x64-off jax would
        # hand back int32 arrays — the guard must see through the alias
        with pytest.raises(ValueError, match="X64"):
            compile_program(prog, dtype=jnp.int64)
    else:
        verify_engine(compile_program(prog, dtype=jnp.int64), prog,
                      n_random=64)

    # a program int32 genuinely covers still accepts an explicit int32
    layer = LUTDense(3, 2, hidden=4)
    small = compile_sequential([layer], [layer.init(KEY)], 1, 1)
    assert small.required_width() <= 30
    verify_engine(compile_program(small, dtype=jnp.int32), small,
                  n_random=64)


# --------------------------------------------------------------------------- #
# schedule view invariants
# --------------------------------------------------------------------------- #
def test_schedule_partitions_program():
    l1 = LUTDense(4, 6, hidden=4)
    l2 = LUTDense(6, 2, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([l1, l2], [l1.init(k1), l2.init(k2)],
                              IN_F, IN_I)
    groups = prog.schedule()
    seen = np.concatenate([g.regs for g in groups])
    assert sorted(seen.tolist()) == list(range(prog.n_instrs()))
    # every group's arguments are produced at a strictly earlier level
    level = np.empty(prog.n_instrs(), np.int64)
    for g in groups:
        level[g.regs] = g.level
    for g in groups:
        for key in ("src", "a", "b"):
            if key in g.args:
                assert (level[g.args[key]] < g.level).all()
    # segments metadata chains the layers
    assert [s.kind for s in prog.segments] == ["lut", "lut"]
    assert prog.segments[0].out_regs == prog.segments[1].in_regs
    assert tuple(prog.outputs) == prog.segments[-1].out_regs
