"""Persistent compiled-artifact bundles: lower once, cold-start forever.

``launch/serve.py --engine tables`` used to re-run the whole pipeline on
every invocation — extract tables, lower to DAIS, compose the fused
per-layer tables, and re-prove bit-exactness — even when the model had not
changed.  A bundle captures everything after the expensive steps in one
atomic ``.npz``:

* ``prog/*``  — the serialized :class:`~repro.core.dais.DaisProgram`
  (``DaisProgram.to_arrays`` wire format: instructions, register formats,
  per-site segments, truth tables — stored **once per layer** no matter
  how many spatial sites share them),
* ``fused/*`` — the composed per-layer stages
  (:class:`~repro.kernels.lut_serve.FusedStages`: site-shared tables,
  per-site gathers, epilogue ops), when the program fuses,
* ``meta_json`` — format version, the **content hash**, and the
  ``verify_engine`` **attestation** (gate statistics recorded when the
  bundle was written).

Format versions (negotiated by :func:`load_artifact`):

* **v4** (current) — v3 plus the "mac" stage kind (an HGQ layer run as
  an integer multiply-accumulate): its per-position requant parameters,
  incoming register formats and folded weights under ``fused/*``.  Its
  ``packed/*`` entry is the enumerated table it packs to.  A v3 reader
  refuses v4 bundles by version.
* **v3** (read-only) — v2 plus the ``packed/*`` payload: the Pallas
  mega-kernel's bit-packed table layout
  (:class:`~repro.kernels.lut_serve_pallas.PackedStages` — out-shift
  folded, lane-dtype tables, sum-stage coefficients), so an
  ``engine="pallas"`` cold start skips the packing pass.  Only what the
  packing *derives* is stored (lane tables, coefficients, in-shift
  elision flags); the shared gathers/biases/epilogues are reconstructed
  from the ``fused/*`` stage IR they equal.
* **v2** (read-only) — graph-lowered programs with the shared-table
  layout: segments carry the spatial site axis and ``fused/*`` holds the
  generalized stage IR.  Hybrid conv programs fuse and round-trip.  Loads
  with no packed payload; a Pallas engine re-packs from the fused stages.
* **v1** (read-only) — flat sequential programs.  v1 bundles still load
  bit-exactly: the program deserializes through the versioned
  ``DaisProgram.from_arrays``, and the *legacy* ``fused/*`` payload (whose
  layout the v2 stage IR superseded) is ignored — the engine recomposes
  its stages from the program on load, paying one composition pass.  A
  bundle from a *newer* writer is rejected with the version it asked for.

The content hash is a SHA-256 over every data array (name, dtype, shape,
bytes) *and* the canonical JSON of the remaining metadata — attestation
included; :func:`load_artifact` always recomputes it and refuses a bundle
whose stored hash does not match.  This makes bundles **tamper-evident**
against bit-rot, truncation, partial writes, and naive edits (including
edits to the stored attestation), which is the failure class
``--skip-verify-cached`` needs closed: the hash ties the gate statistics
to the exact bytes that passed the gate.  It is *not* an authentication
boundary — the digest lives in the file it protects, so an adversary with
write access can rewrite both payload and hash; keyed signatures are a
deployment concern layered above this format.  When that matters, leave
``--skip-verify-cached`` off and the loaded engine is re-gated like a
fresh compile.

Writes are atomic via the ``ckpt/store`` idiom — serialize to
``<path>.tmp``, then ``os.replace`` — so a crash mid-save never leaves a
half-written bundle where a cold start would find it.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import zipfile
from typing import Any, Dict, Optional

import numpy as np

from repro.core.dais import _MODE_CODES, DaisProgram
from repro.kernels.lut_serve import (EpiOp, FusedStage, FusedStages,
                                     ServeEngine, compile_program,
                                     compose_fused_stages, mac_as_lut)
from repro.kernels.lut_serve_pallas import (PackedStage, PackedStages,
                                            PackError, pack_stages)

logger = logging.getLogger(__name__)

FORMAT_VERSION = 4
_SUPPORTED_VERSIONS = (1, 2, 3, 4)
_STAGE_KINDS = ("lut", "sum", "mac")
_EPI_OPS = ("REQUANT", "CMUL")


class ArtifactError(RuntimeError):
    """Bundle is unreadable, wrong version, or fails its content hash."""


def content_hash(arrays: Dict[str, np.ndarray]) -> str:
    """Order-independent SHA-256 over named arrays (dtype+shape+bytes)."""
    h = hashlib.sha256()
    for name in sorted(arrays):
        a = np.ascontiguousarray(arrays[name])
        h.update(name.encode())
        h.update(str(a.dtype).encode())
        h.update(repr(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _bundle_digest(arrays: Dict[str, np.ndarray], meta_core: dict) -> str:
    """Integrity digest: data arrays + canonical JSON of the core metadata.

    Folding the metadata in means the attestation is tamper-evident too —
    an edited ``meta_json`` with an unchanged data payload still fails the
    check.  (Evident, not proof against an adversary who rewrites the
    stored hash as well — see the module docstring.)
    """
    h = hashlib.sha256()
    h.update(content_hash(arrays).encode())
    h.update(json.dumps(meta_core, sort_keys=True).encode())
    return h.hexdigest()


def _data_arrays(prog: DaisProgram,
                 stages: Optional[FusedStages]) -> Dict[str, np.ndarray]:
    arrays = {f"prog/{k}": v for k, v in prog.to_arrays().items()}
    if stages is not None:
        arrays["fused/n_stages"] = np.asarray([stages.n_stages()], np.int64)
        arrays["fused/out_cols"] = np.asarray(stages.out_cols, np.int64)
        for k, st in enumerate(stages.stages):
            p = f"fused/stage{k}_"
            arrays[p + "kind"] = np.asarray([_STAGE_KINDS.index(st.kind),
                                             st.n_cols], np.int64)
            arrays[p + "gather"] = np.asarray(st.gather, np.int64)
            arrays[p + "bias"] = np.asarray(st.bias, np.int64)
            if st.kind == "lut":
                arrays[p + "in_shift"] = np.asarray(st.in_shift, np.int64)
                arrays[p + "mask"] = np.asarray(st.mask, np.int64)
                arrays[p + "table"] = np.asarray(st.table, np.int64)
                arrays[p + "out_shift"] = np.asarray(st.out_shift, np.int64)
            elif st.kind == "mac":
                arrays[p + "in_fmt"] = np.asarray(st.in_fmt, np.int64)
                arrays[p + "requant"] = np.asarray(st.requant, np.int64)
                arrays[p + "requant_mode"] = np.asarray(
                    [_MODE_CODES.index(st.requant_mode)], np.int64)
                arrays[p + "weight"] = np.asarray(st.weight, np.int64)
            else:
                arrays[p + "shifts"] = np.asarray(st.shifts, np.int64)
                arrays[p + "signs"] = np.asarray(st.signs, np.int64)
            arrays[p + "n_epi"] = np.asarray([len(st.epilogue)], np.int64)
            for m, epi in enumerate(st.epilogue):
                arrays[p + f"epi{m}_op"] = np.asarray(
                    [_EPI_OPS.index(epi.op), _MODE_CODES.index(epi.mode)],
                    np.int64)
                arrays[p + f"epi{m}_params"] = np.asarray(epi.params, np.int64)
    return arrays


def _packed_arrays(packed: PackedStages) -> Dict[str, np.ndarray]:
    """The v3 ``packed/*`` payload: only what :func:`pack_stages` derives.

    Per "lut" stage the out-shift-folded table in its lane dtype plus the
    in-shift-elision flag; per "sum" stage the ``sign << shift``
    coefficients.  Gathers, biases, masks and epilogues are *not* repeated —
    the loader reconstructs them from the ``fused/*`` stage IR they equal.
    """
    arrays = {"packed/n_stages": np.asarray([packed.n_stages()], np.int64)}
    for k, st in enumerate(packed.stages):
        p = f"packed/stage{k}_"
        if st.kind == "lut":
            arrays[p + "table"] = np.asarray(st.table)      # lane dtype
            arrays[p + "flags"] = np.asarray(
                [st.in_shift is not None], np.int64)
        else:
            arrays[p + "coef"] = np.asarray(st.coef, np.int64)
    return arrays


def _packed_from_arrays(arrays: Dict[str, np.ndarray],
                        stages: FusedStages) -> PackedStages:
    """Rebuild :class:`PackedStages` from ``packed/*`` + the fused stage IR."""
    n = int(arrays["packed/n_stages"][0])
    if n != stages.n_stages():
        raise ArtifactError(
            f"packed payload has {n} stages but the fused IR has "
            f"{stages.n_stages()} — bundle is internally inconsistent")
    out = []
    for k, st in enumerate(stages.stages):
        p = f"packed/stage{k}_"
        if st.kind == "mac":             # packed as its enumerated form
            st = mac_as_lut(st)
        common = dict(kind=st.kind, gather=np.asarray(st.gather, np.int64),
                      n_cols=st.n_cols, bias=np.asarray(st.bias, np.int64),
                      epilogue=[EpiOp(op=e.op, mode=e.mode,
                                      params=np.asarray(e.params, np.int64))
                                for e in st.epilogue])
        if st.kind == "lut":
            in_shift = np.asarray(st.in_shift, np.int64)
            out.append(PackedStage(
                **common,
                in_shift=in_shift if bool(arrays[p + "flags"][0]) else None,
                mask=np.asarray(st.mask, np.int64),
                table=arrays[p + "table"]))
        else:
            out.append(PackedStage(**common, coef=arrays[p + "coef"]))
    return PackedStages(stages=out,
                        out_cols=np.asarray(stages.out_cols, np.int64),
                        n_cols0=out[0].n_cols if out else 0)


def _stages_from_arrays(arrays: Dict[str, np.ndarray]) -> FusedStages:
    """Rebuild the stage IR (v2 on) written by :func:`_data_arrays`."""
    n = int(arrays["fused/n_stages"][0])
    stages = []
    for k in range(n):
        p = f"fused/stage{k}_"
        kind_idx, n_cols = (int(v) for v in arrays[p + "kind"])
        kind = _STAGE_KINDS[kind_idx]
        epilogue = []
        for m in range(int(arrays[p + "n_epi"][0])):
            op_idx, mode_idx = (int(v) for v in arrays[p + f"epi{m}_op"])
            epilogue.append(EpiOp(op=_EPI_OPS[op_idx],
                                  mode=_MODE_CODES[mode_idx],
                                  params=arrays[p + f"epi{m}_params"]))
        common = dict(kind=kind, gather=arrays[p + "gather"], n_cols=n_cols,
                      bias=arrays[p + "bias"], epilogue=epilogue)
        if kind == "lut":
            stages.append(FusedStage(
                **common, in_shift=arrays[p + "in_shift"],
                mask=arrays[p + "mask"], table=arrays[p + "table"],
                out_shift=arrays[p + "out_shift"]))
        elif kind == "mac":
            stages.append(FusedStage(
                **common, in_fmt=arrays[p + "in_fmt"],
                requant=arrays[p + "requant"],
                requant_mode=_MODE_CODES[int(arrays[p + "requant_mode"][0])],
                weight=arrays[p + "weight"]))
        else:
            stages.append(FusedStage(
                **common, shifts=arrays[p + "shifts"],
                signs=arrays[p + "signs"]))
    return FusedStages(stages=stages, out_cols=arrays["fused/out_cols"])


def save_artifact(path: str, prog: DaisProgram, *,
                  stages: Optional[FusedStages] = None,
                  packed: Optional[PackedStages] = None,
                  compose: bool = True,
                  attestation: Optional[dict] = None) -> str:
    """Write an atomic bundle; returns its content hash.

    ``stages``: pass the already-composed fused tables if the caller built
    an engine anyway; with ``compose=True`` (default) they are composed here
    when omitted — programs that don't fit the fused pattern simply store no
    ``fused/*`` payload and rebuild on the generic path.

    ``packed``: the Pallas mega-kernel lowering; when omitted it is derived
    here with canonical int64 packing (wrap-identical for any program the
    int32 engine legally runs).  A chain that cannot pack (negative shifts,
    residency budget) stores no ``packed/*`` payload — the bundle still
    loads, and a Pallas engine degrades exactly as a fresh compile would.

    ``attestation``: the dict returned by ``verify_engine`` — stored in the
    bundle metadata as the proof-of-verification that
    ``--skip-verify-cached`` trusts.
    """
    if stages is None and compose:
        # range analysis feeds the composer's lane-narrowing masks so the
        # stored packed/* payload is as narrow as a fresh compile's
        try:
            from repro.core.analysis import analyze_ranges
            ranges = analyze_ranges(prog)
        except Exception as e:
            logger.debug("bundle %s: range analysis unavailable (%s)",
                         path, e)
            ranges = None
        stages, _reason = compose_fused_stages(prog, ranges=ranges)
    if packed is None and stages is not None:
        try:
            packed = pack_stages(stages)
        except PackError as e:
            logger.info("bundle %s: no packed payload (%s)", path, e)
    arrays = _data_arrays(prog, stages)
    if packed is not None:
        arrays.update(_packed_arrays(packed))
    meta_core = {
        "format_version": FORMAT_VERSION,
        "fused": stages is not None,
        "packed": packed is not None,
        "attestation": attestation,
    }
    digest = _bundle_digest(arrays, meta_core)
    meta = {**meta_core, "content_hash": digest}
    arrays["meta_json"] = np.frombuffer(
        json.dumps(meta, sort_keys=True).encode(), np.uint8)
    tmp = path + ".tmp"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(tmp, "wb") as f:
        np.savez(f, **arrays)
    os.replace(tmp, path)
    return digest


@dataclasses.dataclass
class LoadedArtifact:
    prog: DaisProgram
    stages: Optional[FusedStages]
    meta: dict
    content_hash: str    # recomputed at load == meta["content_hash"]
    packed: Optional[PackedStages] = None   # Pallas payload (v3 on)

    @property
    def attestation(self) -> Optional[dict]:
        return self.meta.get("attestation")


def load_artifact(path: str) -> LoadedArtifact:
    """Read + integrity-check a bundle.

    Raises :class:`ArtifactError` when the file is missing a payload, has an
    unknown format version, or — the tamper case — the recomputed content
    hash of the data arrays differs from the one recorded at save time.

    The deserialized program is additionally run through the structural
    verifier (``core/analysis.py``): the content hash only proves the bytes
    are the ones saved, not that they encode a well-formed program — a
    bundle written by a buggy producer (or hand-edited with the digest
    recomputed) is rejected here with located lint diagnostics instead of
    failing deep inside an engine lowering.
    """
    try:
        with np.load(path) as z:
            arrays = {k: z[k] for k in z.files}
    except (OSError, ValueError, zipfile.BadZipFile) as e:
        raise ArtifactError(f"cannot read artifact bundle {path!r}: {e}")
    if "meta_json" not in arrays:
        raise ArtifactError(f"{path!r} has no meta_json — not a bundle")
    meta = json.loads(bytes(arrays.pop("meta_json")).decode())
    version = meta.get("format_version")
    if version not in _SUPPORTED_VERSIONS:
        raise ArtifactError(
            f"{path!r}: format_version {version} "
            f"(this reader understands {_SUPPORTED_VERSIONS})")
    meta_core = {k: v for k, v in meta.items() if k != "content_hash"}
    digest = _bundle_digest(arrays, meta_core)
    if digest != meta.get("content_hash"):
        raise ArtifactError(
            f"{path!r}: content hash mismatch — bundle was modified after "
            f"save (stored {meta.get('content_hash')!r}, actual {digest!r}); "
            f"refusing to serve it")

    prog = DaisProgram.from_arrays(
        {k[len("prog/"):]: v for k, v in arrays.items()
         if k.startswith("prog/")})
    from repro.core.analysis import VerifyError, verify_program
    try:
        verify_program(prog)
    except VerifyError as e:
        raise ArtifactError(
            f"{path!r}: bundle program fails the structural verifier — "
            f"refusing to serve it\n{e}") from e
    stages = None
    packed = None
    if meta.get("fused") and version >= 2:
        stages = _stages_from_arrays(arrays)
        if meta.get("packed") and version >= 3:
            packed = _packed_from_arrays(arrays, stages)
    elif meta.get("fused"):
        # backward-compat rule: v1 bundles stay loadable and bit-exact, but
        # their pre-v2 fused layout is superseded — drop it and let
        # build_engine recompose stages from the (versioned) program
        logger.info("v1 bundle %s: legacy fused payload ignored; stages "
                    "will be recomposed from the program", path)
    return LoadedArtifact(prog=prog, stages=stages, meta=meta,
                          content_hash=digest, packed=packed)


def build_engine(art: LoadedArtifact, *, mesh: Optional[Any] = None,
                 jit: bool = True,
                 engine: Optional[str] = None) -> ServeEngine:
    """Deprecated: use ``repro.serve.api.build(art, EngineSpec(...))``.

    The pre-façade spelling of bundle cold-start (stored ``fused/*`` stages
    and ``packed/*`` payload straight into ``compile_program`` — no
    re-lowering, no composition).  It still works, bit-identically
    (``tests/test_serve_api.py`` pins the parity), but emits a
    :class:`DeprecationWarning`: the façade adds the verify policy, the
    require-flags, and provenance in one call.
    """
    import warnings

    warnings.warn(
        "build_engine(art, ...) is deprecated; use repro.serve.api.build("
        "art, EngineSpec(mesh=..., engine=..., verify=...)).engine",
        DeprecationWarning, stacklevel=2)
    return compile_program(art.prog, mesh=mesh, jit=jit,
                           fuse_layers=True, stages=art.stages,
                           engine=engine, packed=art.packed)
