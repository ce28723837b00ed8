"""Compiled-artifact bundles: round-trip exactness + tamper rejection.

The bundle contract (ISSUE 3): ``save → load → api.build`` must be
bit-exact against both the freshly compiled engine and the DAIS
interpreter — on random inputs and exhaustively for small widths — and a
bundle whose bytes changed after save (tables, program, or the stored
attestation itself) must be rejected via the content hash before it can
reach the engine.
"""

import json

import jax
import numpy as np
import pytest

from repro.core.dais import DaisProgram, compile_sequential
from repro.core.hgq_layers import HGQDense
from repro.core.lut_layers import LUTDense
from repro.core.quant import QuantConfig
from repro.kernels.lut_serve import (compile_program, input_code_bounds,
                                     verify_engine)
from repro.serve.api import EngineSpec, build
from repro.serve.artifact import (ArtifactError, load_artifact,
                                  save_artifact)

KEY = jax.random.PRNGKey(23)
IN_F, IN_I = 4, 2


def _engine(art, **spec_kw):
    """Bundle cold-start through the facade (gating is each test's own
    business here, so the spec skips the verify gate)."""
    return build(art, EngineSpec(verify="skip", **spec_kw)).engine


def _lut_stack(dims=(6, 5, 3), hidden=4, key=KEY):
    layers = [LUTDense(ci, co, hidden=hidden, use_batchnorm=(k == 0))
              for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:]))]
    keys = jax.random.split(key, len(layers))
    params = [l.init(k) for l, k in zip(layers, keys)]
    return compile_sequential(layers, params, IN_F, IN_I)


def _narrow_cfg(overflow):
    return QuantConfig(granularity="element", signed=True, overflow=overflow,
                      init_f=1.0, init_i=1.0, min_f=-2, max_f=2,
                      min_i=-2, max_i=2)


# --------------------------------------------------------------------------- #
# DaisProgram wire format round trip
# --------------------------------------------------------------------------- #
def test_program_arrays_round_trip_lut():
    prog = _lut_stack()
    prog2 = DaisProgram.from_arrays(prog.to_arrays())
    assert [(i.op, i.args) for i in prog2.instrs] == \
           [(i.op, i.args) for i in prog.instrs]
    assert prog2.outputs == prog.outputs
    assert prog2.input_f == prog.input_f
    assert prog2.input_signed == prog.input_signed
    assert prog2.output_f == prog.output_f
    assert prog2.segments == prog.segments
    for lid, t in prog.tables.items():
        t2 = prog2.tables[lid]
        for fld in ("f_in", "i_in", "f_out", "i_out",
                    "in_width", "out_width", "codes"):
            np.testing.assert_array_equal(getattr(t2, fld), getattr(t, fld))
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(0).integers(lo, hi + 1, (128, len(lo)))
    np.testing.assert_array_equal(prog2.run(codes), prog.run(codes))


def test_program_arrays_round_trip_hybrid():
    """HGQ layers exercise CONST/CMUL/ADD/SAT-REQUANT arg shapes too."""
    h1 = HGQDense(5, 4, activation="relu")
    l1 = LUTDense(4, 3, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([h1, l1], [h1.init(k1), l1.init(k2)],
                              IN_F, IN_I)
    prog2 = DaisProgram.from_arrays(prog.to_arrays())
    assert [(i.op, i.args) for i in prog2.instrs] == \
           [(i.op, i.args) for i in prog.instrs]
    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(1).integers(lo, hi + 1, (128, len(lo)))
    np.testing.assert_array_equal(prog2.run(codes), prog.run(codes))


def test_from_arrays_rejects_unknown_version():
    arrays = _lut_stack().to_arrays()
    arrays["version"] = np.asarray([99], np.int64)
    with pytest.raises(ValueError, match="version"):
        DaisProgram.from_arrays(arrays)


# --------------------------------------------------------------------------- #
# bundle round trip: save -> load -> run, bit-exact
# --------------------------------------------------------------------------- #
def test_bundle_round_trip_bit_exact_random(tmp_path):
    prog = _lut_stack()
    fresh = compile_program(prog)
    gate = verify_engine(fresh, prog, n_random=256)
    path = str(tmp_path / "model.npz")
    digest = save_artifact(path, prog, attestation=gate)

    art = load_artifact(path)
    assert art.content_hash == digest == art.meta["content_hash"]
    assert art.attestation["random"] == 256
    assert art.stages is not None            # pure LUT chain fuses
    loaded = _engine(art)
    assert loaded.fused

    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(2).integers(lo, hi + 1, (512, len(lo)))
    ref = prog.run(codes)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(loaded.run(codes)), np.int64), ref)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(fresh.run(codes)), np.int64), ref)


def test_bundle_round_trip_bit_exact_exhaustive(tmp_path):
    """Narrow widths -> the loaded engine passes the full exhaustive gate."""
    layer = LUTDense(3, 4, hidden=4,
                     q_in=_narrow_cfg("WRAP"), q_out=_narrow_cfg("SAT"))
    prog = compile_sequential([layer], [layer.init(jax.random.PRNGKey(7))],
                              1, 1)
    path = str(tmp_path / "small.npz")
    save_artifact(path, prog)
    loaded = _engine(load_artifact(path))
    stats = verify_engine(loaded, prog, n_random=64, exhaustive_limit=1024)
    assert stats["exhaustive"] == 512        # 8**3 input cross-product


def test_hybrid_bundle_round_trips_with_fused_stages(tmp_path):
    """Hybrid programs fuse under v2: the bundle persists the composed
    stages (relu epilogue included) and the cold-started engine is
    bit-exact on the fused path."""
    h1 = HGQDense(5, 4, activation="relu")
    l1 = LUTDense(4, 3, hidden=4)
    k1, k2 = jax.random.split(KEY)
    prog = compile_sequential([h1, l1], [h1.init(k1), l1.init(k2)],
                              IN_F, IN_I)
    path = str(tmp_path / "hybrid.npz")
    save_artifact(path, prog)
    art = load_artifact(path)
    assert art.stages is not None and art.stages.n_stages() == 2
    loaded = _engine(art)
    assert loaded.path == "fused"
    verify_engine(loaded, art.prog, n_random=256)


def test_bundle_without_fused_payload_falls_back(tmp_path):
    """compose=False stores no fused payload; the loaded engine recomposes
    (or falls back) and still serves bit-exactly."""
    prog = _lut_stack()
    path = str(tmp_path / "nofuse.npz")
    save_artifact(path, prog, compose=False)
    art = load_artifact(path)
    assert art.stages is None
    loaded = _engine(art)       # recomposed from the program on load
    verify_engine(loaded, art.prog, n_random=256)


def _hybrid_conv_prog():
    from repro.core.hgq_layers import HGQConv1D
    from repro.core.lower import GraphInput, ModelGraph, WindowSum, lower
    from repro.core.lut_layers import LUTConv1D

    front = HGQConv1D(c_in=1, c_out=3, kernel=4, stride=4, activation="relu")
    lc = LUTConv1D(c_in=3, c_out=3, kernel=3, padding="SAME", hidden=4)
    head = LUTDense(3, 1, hidden=4)
    ks = jax.random.split(KEY, 3)
    params = [front.init(ks[0]), lc.init(ks[1]), head.init(ks[2])]
    graph = ModelGraph(GraphInput((16, 1), IN_F, IN_I),
                       [front, lc, head, WindowSum()])
    return lower(graph, params + [None])


def test_conv_hybrid_bundle_round_trip_v2(tmp_path):
    """Acceptance: the current bundle format round-trips the hybrid conv
    program (shared conv tables, hgq stage, window sum) bit-exactly on the
    fused path."""
    prog = _hybrid_conv_prog()
    fresh = compile_program(prog)
    gate = verify_engine(fresh, prog, n_random=256)
    path = str(tmp_path / "hybrid_conv.npz")
    save_artifact(path, prog, attestation=gate)

    art = load_artifact(path)
    assert art.meta["format_version"] == 4
    assert art.stages is not None and art.stages.n_stages() == 4
    loaded = _engine(art)
    assert loaded.path == "fused"

    lo, hi = input_code_bounds(prog)
    codes = np.random.default_rng(7).integers(lo, hi + 1, (256, len(lo)))
    ref = prog.run(codes)
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(loaded.run(codes)), np.int64), ref)


def _same_packed(a, b):
    assert a.n_stages() == b.n_stages()
    for sa, sb in zip(a.stages, b.stages):
        assert sa.kind == sb.kind
        for name in ("gather", "bias", "in_shift", "mask", "table", "coef"):
            va, vb = getattr(sa, name), getattr(sb, name)
            assert (va is None) == (vb is None), name
            if va is not None:
                assert np.asarray(va).dtype == np.asarray(vb).dtype, name
                np.testing.assert_array_equal(va, vb, err_msg=name)


def test_mac_stage_bundle_v4_round_trips(tmp_path):
    """A v4 bundle stores the hybrid's "mac" front (requants, formats,
    folded weights) and its packed enumerated form; both engines built
    from it are bit-exact."""
    from repro.core.analysis import analyze_ranges
    from repro.kernels.lut_serve import compose_fused_stages
    from repro.kernels.lut_serve_pallas import pack_stages

    prog = _hybrid_conv_prog()
    fresh = compile_program(prog)
    assert fresh.stage_kinds == ("mac", "lut", "lut", "sum")
    path = str(tmp_path / "mac.npz")
    save_artifact(path, prog)
    art = load_artifact(path)
    assert art.meta["format_version"] == 4 and art.meta["packed"]
    st = art.stages.stages[0]
    assert st.kind == "mac" and st.requant_mode == "SAT"
    assert st.table is None and st.weight.shape == (4, 3)
    stages, _ = compose_fused_stages(prog, ranges=analyze_ranges(prog))
    _same_packed(art.packed, pack_stages(stages))

    lo, hi = input_code_bounds(prog)
    codes = np.concatenate([
        np.random.default_rng(3).integers(lo, hi + 1, (256, len(lo))),
        np.stack([lo, hi])])
    ref = prog.run(codes)
    for engine in ("fused", "pallas"):
        loaded = _engine(art, engine=engine)
        assert loaded.path == engine and loaded.fuse_reason == ""
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(loaded.run(codes)), np.int64), ref)


def test_v3_bundle_still_loads(tmp_path, monkeypatch):
    """A v3 bundle, written before the "mac" kind (its HGQ front an
    enumerated "lut" stage), loads as it did and serves bit-exactly."""
    from repro.kernels import lut_serve
    from repro.serve.artifact import _bundle_digest

    prog = _hybrid_conv_prog()
    path = str(tmp_path / "v3.npz")
    with monkeypatch.context() as m:
        m.setattr(lut_serve, "_mac_fields", lambda *a: None)
        save_artifact(path, prog)

    def as_v3(arrays):
        meta = json.loads(bytes(arrays.pop("meta_json")).decode())
        meta_core = {k: v for k, v in meta.items() if k != "content_hash"}
        meta_core["format_version"] = 3
        digest = _bundle_digest(arrays, meta_core)
        arrays["meta_json"] = np.frombuffer(json.dumps(
            {**meta_core, "content_hash": digest}, sort_keys=True).encode(),
            np.uint8)
    _rewrite(path, as_v3)

    art = load_artifact(path)
    assert art.meta["format_version"] == 3 and art.packed is not None
    assert [st.kind for st in art.stages.stages] == ["lut", "lut", "lut", "sum"]
    for engine in ("fused", "pallas"):
        loaded = _engine(art, engine=engine)
        assert loaded.path == engine
        verify_engine(loaded, prog, n_random=128)


def test_v1_bundle_negotiated(tmp_path):
    """Backward compat: a v1 bundle (pre-site wire format, legacy fused
    layout) still loads; its fused payload is superseded, so stages are
    recomposed from the program and serving stays bit-exact."""
    from repro.serve.artifact import _bundle_digest

    prog = _lut_stack()
    arrays = {f"prog/{k}": v for k, v in prog.to_arrays().items()}
    # downgrade the program arrays to wire v1
    arrays["prog/version"] = np.asarray([1], np.int64)
    arrays["prog/seg_meta"] = arrays["prog/seg_meta"][:, :4]
    # legacy fused payload (v1 layout the v2 reader must ignore)
    arrays["fused/n_stages"] = np.asarray([1], np.int64)
    arrays["fused/table0"] = np.zeros((2, 2, 2), np.int64)
    meta_core = {"format_version": 1, "fused": True, "attestation": None}
    digest = _bundle_digest(arrays, meta_core)
    meta = {**meta_core, "content_hash": digest}
    arrays["meta_json"] = np.frombuffer(
        json.dumps(meta, sort_keys=True).encode(), np.uint8)
    path = str(tmp_path / "legacy.npz")
    np.savez(path, **arrays)

    art = load_artifact(path)
    assert art.meta["format_version"] == 1
    assert art.stages is None               # legacy fused layout dropped
    loaded = _engine(art)              # recomposes from the program
    verify_engine(loaded, art.prog, n_random=256)


# --------------------------------------------------------------------------- #
# tampering: any post-save modification fails the content hash
# --------------------------------------------------------------------------- #
def _rewrite(path, mutate):
    with np.load(path) as z:
        arrays = {k: z[k].copy() for k in z.files}
    mutate(arrays)
    np.savez(path, **arrays)


def test_tampered_table_rejected(tmp_path):
    prog = _lut_stack()
    path = str(tmp_path / "model.npz")
    save_artifact(path, prog)

    def flip_table_entry(arrays):
        key = next(k for k in arrays if k.startswith("prog/table")
                   and k.endswith("codes"))
        arrays[key][0, 0, 0] += 1
    _rewrite(path, flip_table_entry)
    with pytest.raises(ArtifactError, match="hash mismatch"):
        load_artifact(path)


def test_tampered_fused_stage_rejected(tmp_path):
    prog = _lut_stack()
    path = str(tmp_path / "model.npz")
    save_artifact(path, prog)

    def flip_fused(arrays):
        arrays["fused/stage0_table"][0, 0, 0] ^= 1
    _rewrite(path, flip_fused)
    with pytest.raises(ArtifactError, match="hash mismatch"):
        load_artifact(path)


def test_tampered_hybrid_bundle_rejected(tmp_path):
    """Hybrid v2 bundles stay tamper-evident: program tables, composed
    stage payloads, and epilogue params are all under the content hash."""
    prog = _hybrid_conv_prog()
    path = str(tmp_path / "hybrid_conv.npz")
    save_artifact(path, prog)
    for key_suffix in ("_gather", "_bias"):
        def flip(arrays, suffix=key_suffix):
            key = next(k for k in arrays if k.startswith("fused/stage")
                       and k.endswith(suffix))
            arrays[key].flat[0] += 1
        _rewrite(path, flip)
        with pytest.raises(ArtifactError, match="hash mismatch"):
            load_artifact(path)
        save_artifact(path, prog)        # restore for the next mutation


def test_forged_attestation_rejected(tmp_path):
    """--skip-verify-cached trusts the stored attestation, so editing it
    (without touching a single data array) must still fail the hash."""
    prog = _lut_stack()
    path = str(tmp_path / "model.npz")
    save_artifact(path, prog, attestation={"random": 16, "exhaustive": 0})

    def forge(arrays):
        meta = json.loads(bytes(arrays["meta_json"]).decode())
        meta["attestation"]["random"] = 10**9      # "trust me"
        arrays["meta_json"] = np.frombuffer(
            json.dumps(meta, sort_keys=True).encode(), np.uint8)
    _rewrite(path, forge)
    with pytest.raises(ArtifactError, match="hash mismatch"):
        load_artifact(path)


# --------------------------------------------------------------------------- #
# tampering, round 2: a rewritten hash gets past the digest — the
# structural verifier is the next gate (ISSUE: don't trust prog/* arrays)
# --------------------------------------------------------------------------- #
def _rewrite_rehash(path, mutate):
    """Mutate arrays AND recompute the stored digest, as an adversary with
    write access would — the load must then fall through to the verifier."""
    from repro.serve.artifact import _bundle_digest

    with np.load(path) as z:
        arrays = {k: z[k].copy() for k in z.files}
    meta = json.loads(bytes(arrays.pop("meta_json")).decode())
    mutate(arrays)
    meta_core = {k: v for k, v in meta.items() if k != "content_hash"}
    meta["content_hash"] = _bundle_digest(arrays, meta_core)
    arrays["meta_json"] = np.frombuffer(
        json.dumps(meta, sort_keys=True).encode(), np.uint8)
    np.savez(path, **arrays)


def test_rehashed_out_of_range_register_rejected(tmp_path):
    from repro.core.dais import _OP_CODES

    prog = _lut_stack()
    path = str(tmp_path / "model.npz")
    save_artifact(path, prog)

    def dangling_arg(arrays):
        ops = arrays["prog/instr_op"]
        idx = int(np.flatnonzero(ops == _OP_CODES.index("REQUANT"))[0])
        arrays["prog/instr_args"][idx, 0] = 10**6    # register that never is
    _rewrite_rehash(path, dangling_arg)
    with pytest.raises(ArtifactError, match="structural verifier"):
        load_artifact(path)


def test_rehashed_oversized_llut_index_rejected(tmp_path):
    prog = _lut_stack()
    path = str(tmp_path / "model.npz")
    save_artifact(path, prog)

    def oversize(arrays):
        key = next(k for k in arrays if k.startswith("prog/table")
                   and k.endswith("_in_width"))
        arrays[key] = arrays[key] + 7    # 1 << m now exceeds codes.shape[2]
    _rewrite_rehash(path, oversize)
    with pytest.raises(ArtifactError, match="structural verifier"):
        load_artifact(path)


# --------------------------------------------------------------------------- #
# rtl attestation: bundles carry (and protect) the hardware-level proof
# --------------------------------------------------------------------------- #
def test_rtl_attestation_round_trips(tmp_path):
    """A bundle saved with an 'rtl' attestation entry returns it intact,
    and the stored Verilog hash matches what the loaded program re-emits —
    the bundle pins exactly WHICH hardware passed the three-way gate."""
    import hashlib

    from repro.core.rtl import emit_verilog, verify_rtl

    prog = _lut_stack(dims=(4, 4, 2))
    engine = compile_program(prog)
    gate = verify_engine(engine, prog, n_random=128)
    gate["rtl"] = verify_rtl(prog, engine=engine, n_random=64)
    path = str(tmp_path / "attested.npz")
    save_artifact(path, prog, attestation=gate)

    art = load_artifact(path)
    rtl = art.attestation["rtl"]
    assert rtl["verdict"] == "bit-exact"
    assert rtl["random"] == 64 and rtl["engine_path"] == engine.path
    assert rtl["verilog_sha256"] == hashlib.sha256(
        emit_verilog(art.prog).encode()).hexdigest()


def test_tampered_rtl_attestation_rejected(tmp_path):
    """Swapping the attested Verilog hash (e.g. to pass off different RTL
    as verified) breaks the bundle's content hash."""
    from repro.core.rtl import verify_rtl

    prog = _lut_stack(dims=(4, 4, 2))
    path = str(tmp_path / "attested.npz")
    save_artifact(path, prog,
                  attestation={"random": 16, "exhaustive": 0,
                               "rtl": verify_rtl(prog, n_random=16)})

    def swap_rtl_hash(arrays):
        meta = json.loads(bytes(arrays["meta_json"]).decode())
        meta["attestation"]["rtl"]["verilog_sha256"] = "0" * 64
        arrays["meta_json"] = np.frombuffer(
            json.dumps(meta, sort_keys=True).encode(), np.uint8)
    _rewrite(path, swap_rtl_hash)
    with pytest.raises(ArtifactError, match="hash mismatch"):
        load_artifact(path)


def test_pre_rtl_bundles_still_load(tmp_path):
    """Bundles written before the rtl entry existed (attestation without
    'rtl', or no attestation at all) load and serve unchanged — the entry
    is free-form metadata, not a format bump."""
    prog = _lut_stack(dims=(4, 4, 2))
    path = str(tmp_path / "pre_rtl.npz")
    save_artifact(path, prog, attestation={"random": 32, "exhaustive": 0})
    art = load_artifact(path)
    assert art.meta["format_version"] == 4
    assert "rtl" not in art.attestation
    verify_engine(_engine(art), art.prog, n_random=128)

    save_artifact(path, prog)                # no attestation at all
    art = load_artifact(path)
    assert art.attestation is None
    verify_engine(_engine(art), art.prog, n_random=128)


def test_unreadable_and_versioned_bundles_rejected(tmp_path):
    garbage = tmp_path / "garbage.npz"
    garbage.write_bytes(b"not an npz at all")
    with pytest.raises(ArtifactError, match="cannot read"):
        load_artifact(str(garbage))

    prog = _lut_stack()
    path = str(tmp_path / "model.npz")
    save_artifact(path, prog)

    def bump_version(arrays):
        meta = json.loads(bytes(arrays["meta_json"]).decode())
        meta["format_version"] = 99
        arrays["meta_json"] = np.frombuffer(
            json.dumps(meta, sort_keys=True).encode(), np.uint8)
    _rewrite(path, bump_version)
    with pytest.raises(ArtifactError, match="format_version"):
        load_artifact(str(path))
