"""Serve-side Pallas mega-kernel: bit-exactness, packing, path selection.

The contract under test (ISSUE 6 acceptance): the single-launch bit-packed
engine of ``kernels/lut_serve_pallas.py`` must match both the numpy DAIS
interpreter and the fused per-stage engine code-for-code — exhaustively on
small input spaces, randomly on wide ones, on the hybrid PID conv shape,
and on DCE-sliced programs with pruned table rows — while every path
downgrade surfaces as a compile-time :class:`EnginePathWarning`, and the
packed layout round-trips through the artifact bundle (format v3 on).

On CPU the kernel runs with ``interpret=True`` (auto-selected off-TPU), so
these tests execute the identical kernel logic CI ships.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dais import compile_sequential
from repro.core.hgq_layers import HGQDense
from repro.core.lut_layers import LUTDense
from repro.core.quant import QuantConfig
from repro.kernels.lut_serve import (EnginePathWarning, compile_program,
                                     compose_fused_stages, input_code_bounds,
                                     verify_engine)
from repro.kernels import lut_serve_pallas
from repro.kernels.lut_serve_pallas import (PackError, pack_stages,
                                            pallas_runner)

from _hgq_progs import nonlinear_prog

KEY = jax.random.PRNGKey(11)
IN_F, IN_I = 4, 2


def _narrow_cfg(overflow):
    return QuantConfig(granularity="element", signed=True, overflow=overflow,
                       init_f=1.0, init_i=1.0, min_f=-2, max_f=2,
                       min_i=-2, max_i=2)


def _three_way(prog, codes, **pallas_kw):
    """interpreter == fused engine == pallas engine, code-for-code."""
    ref = prog.run(codes)
    fused = compile_program(prog, engine="fused")
    assert fused.path == "fused"
    pallas = compile_program(prog, engine="pallas", **pallas_kw)
    assert pallas.path == "pallas"
    assert pallas.fused and pallas.fuse_reason == ""
    assert pallas.n_launches == 1
    assert fused.n_launches == fused.n_groups > 0
    for eng in (fused, pallas):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(eng.run(codes)), np.int64), ref)
    return pallas


# --------------------------------------------------------------------------- #
# bit-exactness: exhaustive-small, random-wide, hybrid, DCE-pruned
# --------------------------------------------------------------------------- #
def test_exhaustive_three_way_bit_exact():
    layer = LUTDense(3, 4, hidden=4,
                     q_in=_narrow_cfg("WRAP"), q_out=_narrow_cfg("SAT"))
    prog = compile_sequential([layer], [layer.init(jax.random.PRNGKey(7))],
                              1, 1)                 # 3-bit inputs: 512 rows
    lo, hi = input_code_bounds(prog)
    grids = np.meshgrid(*[np.arange(l, h + 1) for l, h in zip(lo, hi)],
                        indexing="ij")
    codes = np.stack([g.ravel() for g in grids], axis=-1)
    assert codes.shape[0] == 512
    engine = _three_way(prog, codes)
    # the packaged gate agrees and actually sweeps the full input space
    stats = verify_engine(engine, prog, n_random=64, exhaustive_limit=1024)
    assert stats["exhaustive"] == 512


def test_two_layer_random_wide_bit_exact():
    l1 = LUTDense(6, 9, hidden=4, use_batchnorm=True)
    l2 = LUTDense(9, 3, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([l1, l2], [l1.init(k1), l2.init(k2)],
                              IN_F, IN_I)
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(0).integers(lo, hi + 1, (512, len(lo)))
    _three_way(prog, codes)


def _hybrid_conv_prog():
    from repro.core.hgq_layers import HGQConv1D
    from repro.core.lower import GraphInput, ModelGraph, WindowSum, lower
    from repro.core.lut_layers import LUTConv1D

    front = HGQConv1D(c_in=1, c_out=3, kernel=4, stride=4, activation="relu")
    lc = LUTConv1D(c_in=3, c_out=3, kernel=3, padding="SAME", hidden=4)
    head = LUTDense(3, 1, hidden=4)
    ks = jax.random.split(KEY, 3)
    graph = ModelGraph(GraphInput((16, 1), IN_F, IN_I),
                       [front, lc, head, WindowSum()])
    return lower(graph, [front.init(ks[0]), lc.init(ks[1]),
                         head.init(ks[2]), None])


def test_hybrid_conv_graph_bit_exact():
    """The PID shape: HGQ conv front, shared-table LUT convs, window sum."""
    prog = _hybrid_conv_prog()
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(5).integers(lo, hi + 1, (256, len(lo)))
    engine = _three_way(prog, codes)
    verify_engine(engine, prog, n_random=128)


def _prune_q(params, which, mask):
    """Drive quantizer widths of masked cells below zero (width-pruned)."""
    for k in ("f", "i"):
        a = np.array(params[which][k])
        a[mask] = -8.0
        params[which][k] = jnp.asarray(a)
    return params


def _zero_cells(params, mask):
    """Zero the cell MLP output: constant-0 truth table, positive widths."""
    for k in ("w_out", "b_out"):
        a = np.array(params[k], np.float64)
        a[mask] = 0.0
        params[k] = jnp.asarray(a, jnp.float32)
    return params


def test_dce_sliced_program_with_pruned_rows_bit_exact():
    """DCE slices dead table rows/columns; the packed gather and lane tables
    must track the sliced layout, gated against the UNoptimized oracle."""
    from repro.core.opt import eliminate_dead_cells

    rng = np.random.default_rng(2)
    l1 = LUTDense(5, 7, hidden=4, use_batchnorm=True)
    l2 = LUTDense(7, 3, hidden=4)
    k1, k2 = jax.random.split(jax.random.PRNGKey(2))
    p1 = _zero_cells(_prune_q(l1.init(k1), "q_out", rng.random((5, 7)) < 0.3),
                     rng.random((5, 7)) < 0.3)
    p2 = _prune_q(l2.init(k2), "q_in", rng.random((7, 3)) < 0.3)
    prog = compile_sequential([l1, l2], [p1, p2], IN_F, IN_I)
    opt, rep = eliminate_dead_cells(prog)
    assert rep.n_llut_after < rep.n_llut_before     # rows actually pruned
    engine = compile_program(opt, engine="pallas")
    assert engine.path == "pallas"
    verify_engine(engine, prog, n_random=512)       # optimized vs original


# --------------------------------------------------------------------------- #
# packing: lane dtypes, residency budget, shift refusal
# --------------------------------------------------------------------------- #
def test_lane_packing_shrinks_tables():
    l1 = LUTDense(6, 9, hidden=4, use_batchnorm=True)
    l2 = LUTDense(9, 3, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([l1, l2], [l1.init(k1), l2.init(k2)],
                              IN_F, IN_I)
    stages, reason = compose_fused_stages(prog)
    assert stages is not None, reason
    packed = pack_stages(stages)
    # narrow quantized outputs fold+pack into int8 lanes, 4-8x smaller than
    # the int32/int64 entries the fused engine gathers from
    lanes = {str(st.table.dtype) for st in packed.stages
             if st.table is not None}
    assert lanes == {"int8"}
    fused_bytes = sum(np.asarray(st.table, np.int64).nbytes
                      for st in stages.stages if st.kind == "lut")
    assert packed.table_bytes() * 4 <= fused_bytes
    assert packed.resident_bytes() >= packed.table_bytes()


def _packed_arrays(packed):
    """Every field of a PackedStages, by stage and name."""
    out = {"out_cols": packed.out_cols, "n_cols0": np.asarray(packed.n_cols0)}
    for k, st in enumerate(packed.stages):
        for field in dataclasses.fields(st):
            v = getattr(st, field.name)
            if field.name == "epilogue":
                for m, e in enumerate(v):
                    out[f"{k}.epi{m}"] = np.asarray(e.params)
                    out[f"{k}.epi{m}.op"] = np.asarray([e.op, e.mode])
            elif v is not None:
                out[f"{k}.{field.name}"] = np.asarray(v)
    return out


@pytest.mark.parametrize("dtype", [jnp.int32, None], ids=["int32", "int64"])
def test_pack_stages_of_mac_front_matches_enumerated_table(dtype, monkeypatch):
    """The Pallas path packs a "mac" front exactly as it packed the
    enumerated table the composer built for the same chains: the same
    arrays, table lane dtypes included."""
    from repro.core.analysis import analyze_ranges
    from repro.kernels import lut_serve
    from repro.kernels.lut_serve import mac_as_lut

    prog = _hybrid_conv_prog()
    ranges = analyze_ranges(prog)
    mac, why = compose_fused_stages(prog, jnp.int32, ranges=ranges)
    assert mac is not None, why
    assert [st.kind for st in mac.stages] == ["mac", "lut", "lut", "sum"]
    monkeypatch.setattr(lut_serve, "_mac_fields", lambda *a: None)
    enum, why = compose_fused_stages(prog, jnp.int32, ranges=ranges)
    assert enum is not None, why
    assert enum.stages[0].kind == "lut"

    as_lut = mac_as_lut(mac.stages[0])
    for name in ("in_shift", "mask", "table", "out_shift", "live", "bias"):
        np.testing.assert_array_equal(getattr(as_lut, name),
                                      getattr(enum.stages[0], name))
    got, want = (_packed_arrays(pack_stages(st, dtype)) for st in (mac, enum))
    assert got.keys() == want.keys()
    for name in want:
        assert got[name].dtype == want[name].dtype, name
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)


def test_residency_budget_is_a_pack_error():
    layer = LUTDense(4, 3, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)
    stages, _ = compose_fused_stages(prog)
    with pytest.raises(PackError, match="vmem_budget"):
        pack_stages(stages, vmem_budget=16)


def test_pack_failure_falls_back_to_fused_with_warning(monkeypatch):
    """pallas -> fused degradation is loud: EnginePathWarning + fuse_reason,
    and the downgraded engine still serves bit-exactly."""
    layer = LUTDense(4, 3, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)

    def boom(stages, dtype=None, **kw):
        raise PackError("synthetic budget bust")
    monkeypatch.setattr(lut_serve_pallas, "pack_stages", boom)
    with pytest.warns(EnginePathWarning, match="synthetic budget bust"):
        engine = compile_program(prog, engine="pallas")
    assert engine.path == "fused"
    assert "pallas unavailable" in engine.fuse_reason
    verify_engine(engine, prog, n_random=128)


def test_unfusable_program_degrades_to_generic_with_warning():
    # a non-linear chain on an operand too wide to enumerate
    prog = nonlinear_prog(width=24)
    with pytest.warns(EnginePathWarning, match="pallas"):
        engine = compile_program(prog, engine="pallas")
    assert engine.path == "generic" and not engine.fused
    verify_engine(engine, prog, n_random=128)


def test_legacy_fuse_layers_false_stays_quiet():
    """The documented legacy spelling is not a downgrade — no warning."""
    import warnings as _w
    layer = LUTDense(4, 3, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)
    with _w.catch_warnings():
        _w.simplefilter("error", EnginePathWarning)
        engine = compile_program(prog, fuse_layers=False)
    assert engine.path == "generic"
    assert "fuse_layers=False" in engine.fuse_reason


# --------------------------------------------------------------------------- #
# runner mechanics: odd batches through the pad/tile path
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("batch", [1, 7, 65, 300])
def test_odd_batches_pad_and_slice(batch):
    l1 = LUTDense(5, 6, hidden=4)
    l2 = LUTDense(6, 2, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([l1, l2], [l1.init(k1), l2.init(k2)],
                              IN_F, IN_I)
    engine = compile_program(prog, engine="pallas", block_batch=64)
    assert engine.path == "pallas"
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(batch).integers(lo, hi + 1,
                                                  (batch, len(lo)))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(engine.run(codes)), np.int64),
        prog.run(codes))


def test_runner_direct_from_packed_stages():
    """pallas_runner over a hand-packed chain, bypassing compile_program."""
    layer = LUTDense(4, 3, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)
    stages, _ = compose_fused_stages(prog)
    packed = pack_stages(stages)
    run = pallas_runner(packed, jnp.int32)
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(1).integers(lo, hi + 1, (33, len(lo)))
    got = jax.jit(run)(jnp.asarray(codes, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got, np.int64), prog.run(codes))


# --------------------------------------------------------------------------- #
# scheduler + artifact integration
# --------------------------------------------------------------------------- #
def test_scheduler_serves_pallas_engine_and_reports_path():
    from repro.serve.scheduler import MicroBatcher, ServeConfig

    layer = LUTDense(5, 4, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)
    engine = compile_program(prog, engine="pallas")
    assert engine.path == "pallas"
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(3).integers(lo, hi + 1, (40, len(lo)))
    with MicroBatcher(engine, ServeConfig(max_batch=16,
                                          max_delay_ms=1.0)) as mb:
        futs = [mb.submit(c) for c in codes]
        out = np.stack([f.result(timeout=30.0) for f in futs])
        stats = mb.stats()
    np.testing.assert_array_equal(out.astype(np.int64), prog.run(codes))
    assert stats.engine_path == "pallas"


def test_artifact_v3_round_trips_packed_payload(tmp_path):
    from repro.serve.api import EngineSpec, build
    from repro.serve.artifact import load_artifact, save_artifact

    l1 = LUTDense(6, 9, hidden=4, use_batchnorm=True)
    l2 = LUTDense(9, 3, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([l1, l2], [l1.init(k1), l2.init(k2)],
                              IN_F, IN_I)
    path = str(tmp_path / "m.npz")
    save_artifact(path, prog)
    art = load_artifact(path)
    assert art.meta["format_version"] == 4 and art.meta["packed"]
    assert art.packed is not None
    # the stored payload is the lane-packed layout, not a re-derivation
    assert {str(st.table.dtype) for st in art.packed.stages
            if st.table is not None} == {"int8"}
    engine = build(art, EngineSpec(engine="pallas",
                                   verify="skip")).engine
    assert engine.path == "pallas" and engine.fuse_reason == ""
    assert engine.packed_table_bytes == art.packed.table_bytes()
    verify_engine(engine, prog, n_random=256)
    # default build keeps the fused path exactly as before
    assert build(art, EngineSpec(verify="skip")).engine.path == "fused"


def test_v2_bundle_negotiates_without_packed_payload(tmp_path):
    """A pre-v3 bundle (no packed/*) loads, and a pallas engine re-packs."""
    from repro.serve.api import EngineSpec, build
    from repro.serve.artifact import (_bundle_digest, load_artifact,
                                      save_artifact)

    layer = LUTDense(4, 3, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)
    v3 = str(tmp_path / "v3.npz")
    save_artifact(v3, prog)
    with np.load(v3) as z:
        arrays = {k: z[k].copy() for k in z.files
                  if not k.startswith("packed/") and k != "meta_json"}
    meta_core = {"format_version": 2, "fused": True, "attestation": None}
    digest = _bundle_digest(arrays, meta_core)
    arrays["meta_json"] = np.frombuffer(
        json.dumps({**meta_core, "content_hash": digest},
                   sort_keys=True).encode(), np.uint8)
    v2 = str(tmp_path / "v2.npz")
    np.savez(v2, **arrays)

    art = load_artifact(v2)
    assert art.meta["format_version"] == 2 and art.packed is None
    engine = build(art, EngineSpec(engine="pallas",
                                   verify="skip")).engine
    assert engine.path == "pallas"          # re-packed from fused stages
    verify_engine(engine, prog, n_random=128)


# --------------------------------------------------------------------------- #
# launcher enforcement: --require-pallas / --require-fused fail loudly
# --------------------------------------------------------------------------- #
def test_require_flags_fail_loudly():
    """--require-pallas/--require-fused map to EngineSpec.require, and a
    path downgrade is a hard EngineRequirementError, not a warning."""
    import argparse

    from repro.launch.serve import _spec
    from repro.serve.api import EngineRequirementError, EngineSpec, build

    layer = LUTDense(4, 3, hidden=4)
    prog = compile_sequential([layer], [layer.init(KEY)], IN_F, IN_I)
    ns = lambda **kw: argparse.Namespace(
        **{"engine": "tables", "require_fused": False,
           "require_pallas": False, "smoke": True, "seed": 0, **kw})
    assert _spec(ns(), None, verify="full").require is None
    assert _spec(ns(require_fused=True), None, verify="full").require == "fused"
    spec = _spec(ns(require_pallas=True), None, verify="full")
    assert spec.require == "pallas" and spec.engine == "pallas"
    # the generic lowering cannot satisfy either require flag
    with pytest.raises(EngineRequirementError, match="pallas"):
        build(prog, EngineSpec(engine="groups", require="pallas",
                               verify="skip"))
    with pytest.raises(EngineRequirementError, match="fused"):
        build(prog, EngineSpec(engine="groups", require="fused",
                               verify="skip"))
    # satisfied requirements build normally
    assert build(prog, dataclasses.replace(
        spec, n_random=64)).engine.path == "pallas"
