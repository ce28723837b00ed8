"""Serve-side Pallas mega-kernel: the whole stage chain in ONE launch.

The fused engine of ``kernels/lut_serve.py`` is already a single jitted
function, but XLA lowers it as a chain of full-batch ops: every stage
materializes its ``(B, S, J, co)`` requant/gather intermediates before the
next stage starts, so at production batch sizes the inter-stage activations
round-trip through HBM (on CPU: blow out the cache) once per stage.  This
module executes the *entire* :class:`~repro.kernels.lut_serve.FusedStages`
chain inside one ``pl.pallas_call``: per batch tile, site-gather → requant
→ table-gather → Σ → epilogue for every stage back to back, with the
inter-stage values living in the tile's registers/VMEM and only the input
codes and final output codes touching HBM.

Packing (:func:`pack_stages` → :class:`PackedStages`)
-----------------------------------------------------
The compile-time lowering from ``FusedStages``, done once per engine:

* **out-shift folding** — a "lut" stage's per-cell alignment shift
  (``table[...] << out_shift``, an extra op over the full ``(B,S,J,co)``
  gather result) is applied to the *table entries* at pack time.  Exact:
  the runtime sums the same shifted magnitudes the fused engine computes.
* **int8/int16/int32 lane packing** — each stage's (DCE-sliced, post
  ``core/opt.py`` row slicing) shared table is stored in the narrowest
  signed lane dtype holding every folded entry; the kernel's gather reads
  the lane and **sign-extends** (``astype`` to the compute dtype).  Tables
  the fused engine keeps at 4–8 B/entry typically pack to 1 B/entry, which
  is what makes whole-chain table residency realistic.
* **range-driven lane narrowing** — when the stage carries a ``live``
  entry mask (from the interval analysis of ``core/analysis.py``, threaded
  through ``compose_fused_stages``), entries proven unreachable under the
  input contract are zeroed *before* lane selection and a fully-dead
  trailing index span is sliced off.  The dead entries are typically the
  saturation rows holding the largest-magnitude codes — exactly the values
  that force a wider lane — so proving them dead is what turns an int16
  table into an int8 one (``docs/ir.md``).
* **"mac" stages run enumerated** — an HGQ stage the fused engine runs
  as an integer multiply-accumulate is expanded back into its enumerated
  table (:func:`~repro.kernels.lut_serve.mac_as_lut`) and packed as a
  "lut" stage.
* **in-shift elision** — stages whose per-cell input grids already match
  (every ``in_shift == 0`` — all enumerated HGQ stages, and LUT stages
  whose incoming grid equals the table grid) statically skip the
  round-half-to-even ``_shift_round`` block, the widest intermediate of
  the fused runtime.
* **sum-stage coefficients** — a table-free stage's ``sign * (v << shift)``
  becomes one multiply by the precomputed ``coef = sign << shift``
  (alignment shifts are non-negative by construction; packing refuses
  otherwise rather than guess).
* **residency budget** — packing fails with :exc:`PackError` (and the
  engine falls back to the fused path, never silently) when the packed
  tables + stage constants exceed ``vmem_budget`` bytes: a chain whose
  tables cannot stay resident gains nothing from a single launch.

Execution (:func:`pallas_runner`)
---------------------------------
Grid = 1-D over batch tiles (``block_batch`` rows per program instance,
shrunk to the padded batch for small scheduler buckets).  The stage loop is
statically unrolled inside the kernel, and every value in it is 2-D so that
Mosaic lowers it: gather/output indices are baked in as static column
slices, and a table lookup is a loop over the table's entries that selects
each entry where the index matches.  Tables (entry-major, in their lane
dtype), masks, shifts, biases and epilogue parameters arrive as full-array
block inputs (VMEM-resident across the chain).  A
second grid axis over stage width is deliberately absent: stages are
all-to-all (every output column may read any input column), so a width
tile would have to re-materialize the full inter-stage vector anyway —
width stays a vector axis inside the tile and the residency budget bounds
it instead.  Bit-exactness reuses the same ``_shift_round`` /
``_requant_cols`` primitives as the fused engine and is gated by the same
``verify_engine`` before anything serves or is benchmarked.

On a TPU the kernel compiles through Mosaic; on any other backend it runs
with ``interpret=True`` (under ``jit`` this still compiles to XLA), so CPU
tests execute the identical kernel logic.  On a multi-device mesh the call
runs under ``shard_map``, one batch shard per device.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.lut_serve import (EpiOp, FusedStages, _ComposeError,
                                     _requant_cols, _shift_round, mac_as_lut)

# default batch tile: big enough to amortize the grid step, small enough
# that a few stages of (TB, S, co) intermediates stay cache/VMEM-resident
# (picked by sweeping 64..1024 at batch 1024 on the bench models)
DEF_BLOCK_BATCH = 512

# VMEM for one tile's intermediates.  Compiling pid-hybrid (ctx=100) for a
# TPU v5e showed about a dozen live int32 copies of the widest stage's
# (TB, S * lane-padded J*co) index/value blocks; a tile is sized so that
# they fit this, inside the 16 MiB default scoped VMEM with the tables.
DEF_TILE_VMEM = 8 << 20
_LIVE_BLOCKS = 12

# packed tables + stage constants must fit comfortably in VMEM (~16 MB on
# current TPUs) with room for the batch tile and its intermediates
DEF_VMEM_BUDGET = 8 << 20


class PackError(Exception):
    """The stage chain cannot be packed; message is the fallback reason."""


@dataclasses.dataclass
class PackedStage:
    """One stage of the mega-kernel, constants pre-folded and lane-packed.

    Mirrors :class:`~repro.kernels.lut_serve.FusedStage` with the runtime
    work moved to pack time: ``table`` holds the out-shift-folded entries
    in the narrowest signed lane dtype (sign-extended on read),
    ``in_shift`` is ``None`` when the whole stage needs no input requant,
    and a "sum" stage carries the single ``coef`` multiplier instead of
    (signs, shifts).
    """

    kind: str                    # "lut" | "sum"
    gather: np.ndarray           # (S, J) int64; == n_cols -> zero column
    n_cols: int                  # incoming flat width
    bias: np.ndarray             # (S, co)
    epilogue: List[EpiOp]
    # kind "lut"
    in_shift: Optional[np.ndarray] = None  # (J, co); None == all zero
    mask: Optional[np.ndarray] = None      # (J, co)
    table: Optional[np.ndarray] = None     # (J, co, E), lane dtype
    # kind "sum"
    coef: Optional[np.ndarray] = None      # (S, J) = sign << shift

    @property
    def n_sites(self) -> int:
        return self.gather.shape[0]

    @property
    def c_out(self) -> int:
        return self.bias.shape[1]


@dataclasses.dataclass
class PackedStages:
    """The packed lowering of a :class:`FusedStages` chain (plain data).

    Persisted by the compiled-artifact bundle (format v3) so a cold start
    skips the packing pass; :func:`pallas_runner` turns it into the
    single-launch runtime.
    """

    stages: List[PackedStage]
    out_cols: np.ndarray         # (n_outputs,) columns of the final stage
    n_cols0: int                 # input width of the first stage

    def n_stages(self) -> int:
        return len(self.stages)

    def table_bytes(self) -> int:
        """Bytes of packed (lane-dtype, out-shift-folded) tables."""
        return int(sum(st.table.nbytes for st in self.stages
                       if st.table is not None))

    def resident_bytes(self) -> int:
        """Everything the kernel keeps resident: tables + stage constants."""
        total = 0
        for st in self.stages:
            for a in (st.table, st.mask, st.in_shift, st.bias, st.coef,
                      st.gather):
                if a is not None:
                    total += a.nbytes
            total += sum(np.asarray(e.params).nbytes for e in st.epilogue)
        return total


def _lane_dtype(a: np.ndarray, ed) -> np.dtype:
    """Narrowest signed integer dtype holding every value of ``a``.

    Bounded above by the engine dtype ``ed`` — a table whose folded values
    need more bits than the engine computes in would already be an
    overflow bug upstream.
    """
    if a.size == 0:
        return np.dtype(np.int8)
    lo, hi = int(a.min()), int(a.max())
    for dt in (np.int8, np.int16, np.int32):
        info = np.iinfo(dt)
        if lo >= info.min and hi <= info.max \
                and np.dtype(dt).itemsize <= np.dtype(ed).itemsize:
            return np.dtype(dt)
    return np.dtype(ed)


def pack_stages(stages: FusedStages, dtype: Optional[object] = None, *,
                vmem_budget: int = DEF_VMEM_BUDGET) -> PackedStages:
    """Lower composed stages to the packed mega-kernel layout.

    ``dtype`` is the engine compute dtype (int32/int64); ``None`` packs
    with int64 arithmetic, which is wrap-identical for any program the
    int32 engine legally runs (the proven ``engine_width`` — or its
    ``required_width()`` fallback — bounds every transient).  Stages
    carrying a ``live`` mask get range-driven lane narrowing (see module
    docstring).  Raises :exc:`PackError` when the chain cannot be packed
    faithfully or busts the residency budget.
    """
    ed = np.int32 if (dtype is not None
                      and jnp.dtype(dtype) == jnp.dtype(jnp.int32)) \
        else np.int64
    packed: List[PackedStage] = []
    for st in stages.stages:
        if st.kind == "mac":
            try:
                st = mac_as_lut(st)
            except _ComposeError as e:
                raise PackError(f"mac stage cannot run enumerated: {e}")
        bias = np.asarray(st.bias, np.int64).astype(ed)
        epis = [EpiOp(op=e.op, mode=e.mode,
                      params=np.asarray(e.params, np.int64))
                for e in st.epilogue]
        if st.kind == "lut":
            out_shift = np.asarray(st.out_shift, np.int64)
            if (out_shift < 0).any():
                raise PackError("negative out_shift cannot fold into a table")
            # fold the per-cell alignment shift into the entries, in engine
            # arithmetic so any wrap matches the fused runtime bit-for-bit
            shifted = np.asarray(st.table, np.int64).astype(ed) \
                << out_shift.astype(ed)[:, :, None]
            live = getattr(st, "live", None)
            if live is not None:
                live = np.asarray(live, bool)
                if live.shape != shifted.shape:
                    raise PackError(
                        f"live mask shape {live.shape} != table "
                        f"shape {shifted.shape}")
                # proven-dead entries can hold anything without changing
                # any in-contract result; zero is the narrowest choice
                shifted = np.where(live, shifted, 0)
                reach = np.flatnonzero(live.any(axis=(0, 1)))
                e_live = int(reach[-1]) + 1 if reach.size else 1
                if e_live < shifted.shape[2]:
                    shifted = shifted[:, :, :e_live]
            in_shift = np.asarray(st.in_shift, np.int64)
            packed.append(PackedStage(
                kind="lut", gather=np.asarray(st.gather, np.int64),
                n_cols=st.n_cols, bias=bias, epilogue=epis,
                in_shift=None if not in_shift.any() else in_shift,
                mask=np.asarray(st.mask, np.int64),
                table=shifted.astype(_lane_dtype(shifted, ed))))
        elif st.kind == "sum":
            shifts = np.asarray(st.shifts, np.int64)
            if (shifts < 0).any():
                raise PackError("negative alignment shift in a sum stage")
            coef = np.asarray(st.signs, np.int64).astype(ed) \
                << shifts.astype(ed)
            packed.append(PackedStage(
                kind="sum", gather=np.asarray(st.gather, np.int64),
                n_cols=st.n_cols, bias=bias, epilogue=epis, coef=coef))
        else:
            raise PackError(f"unknown stage kind {st.kind!r}")
    out = PackedStages(stages=packed,
                       out_cols=np.asarray(stages.out_cols, np.int64),
                       n_cols0=packed[0].n_cols if packed else 0)
    resident = out.resident_bytes()
    if resident > vmem_budget:
        raise PackError(
            f"packed tables + constants need {resident} bytes resident "
            f"(> vmem_budget={vmem_budget}); the chain cannot stay "
            f"table-resident in one launch")
    return out


# --------------------------------------------------------------------------- #
# the kernel
# --------------------------------------------------------------------------- #
# Every value inside the kernel is 2-D (rows, flat columns): Mosaic lowers
# static lane slices, lane concatenation and elementwise ops, but neither a
# general gather nor a reshape that moves the lane axis.  So the per-site
# column gather becomes static slices (its indices are compile-time
# constants), and a "lut" stage lays its cells out flat as column
# ``j * co + i`` of one site's (TB, J*co) block.

def _rows_per_tile(dtype) -> int:
    """Sublane rows of one native VMEM tile: 8 for 32-bit, 32 for int8."""
    return 32 // np.dtype(dtype).itemsize


def _const_arrays(packed: PackedStages, cdtype):
    """Flatten per-stage constants into one input list + name->index maps.

    Each array is 2-D in the layout the kernel reads.  A table becomes
    ``(E, J*co)`` (entry-major, cells flat) in its packed lane dtype,
    padded to whole VMEM tiles with never-indexed zero rows, and is
    sign-extended inside the kernel; everything else is a ``(rows,
    S*co)``/``(1, J*co)`` row block coerced to the compute dtype, so a
    bundle packed under a different x64 setting still runs.  Gather and
    output columns are compile-time constants and stay out of the list.
    """
    ed = np.int32 if jnp.dtype(cdtype) == jnp.dtype(jnp.int32) else np.int64
    arrays: List[np.ndarray] = []
    entries: List[dict] = []

    def row(a, rows=1):
        return np.asarray(a, np.int64).astype(ed).reshape(rows, -1)

    for st in packed.stages:
        ent = {}

        def add(name, a, _ent=ent):
            _ent[name] = len(arrays)
            arrays.append(a)

        add("bias", row(st.bias))
        if st.kind == "lut":
            if st.in_shift is not None:
                add("in_shift", row(st.in_shift))
            add("mask", row(st.mask))
            table = np.asarray(st.table)                # (J, co, E) lane
            flat = table.reshape(-1, table.shape[2]).T  # (E, J*co)
            pad = -flat.shape[0] % _rows_per_tile(flat.dtype)
            add("table", np.pad(flat, ((0, pad), (0, 0))))
        for m, e in enumerate(st.epilogue):
            p = np.asarray(e.params, np.int64)
            # REQUANT (S, co, 4) -> 4 rows (shift, width, signed, apply)
            add(f"epi{m}", row(np.moveaxis(p, -1, 0), 4) if e.op == "REQUANT"
                else row(p))
        entries.append(ent)
    return arrays, entries


def _column(v, c: int, n_cols: int):
    """Static column ``c`` of ``v`` as (TB, 1); ``n_cols`` is the zero pad."""
    if c >= n_cols:
        return jnp.zeros((v.shape[0], 1), v.dtype)
    return v[:, c:c + 1]


def _lookup(idxs, t_ref, dtype):
    """``table[idx]`` per column for each (TB, C) index block in ``idxs``.

    A loop over the table's entries, one VMEM tile of rows per step: each
    row is compared against the indices and selected where it matches.
    Exact for any lane dtype (the select copies the sign-extended entry),
    and it needs no gather, which Mosaic lowers only in 2-D special cases.
    """
    rows = _rows_per_tile(t_ref.dtype)

    def body(k, vals):
        base = pl.multiple_of(k * rows, rows)
        tile = t_ref[pl.ds(base, rows), :].astype(dtype)   # sign-extend
        vals = list(vals)
        for q in range(rows):
            entry = tile[q:q + 1, :]                        # (1, C)
            for s, idx in enumerate(idxs):
                vals[s] = jnp.where(idx == base + q, entry, vals[s])
        return tuple(vals)

    init = tuple(jnp.zeros(idx.shape, dtype) for idx in idxs)
    return jax.lax.fori_loop(0, t_ref.shape[0] // rows, body, init)


def _make_kernel(packed: PackedStages, entries):
    """Build the mega-kernel body: the stage loop, statically unrolled."""

    def kernel(*refs):
        x_ref, consts, out_ref = refs[0], refs[1:-1], refs[-1]
        v = x_ref[...]                                  # (TB, n_cols0)
        for st, ent in zip(packed.stages, entries):
            tb, dt = v.shape[0], v.dtype
            gather = np.asarray(st.gather)
            sites = [[_column(v, int(c), st.n_cols) for c in gather[s]]
                     for s in range(st.n_sites)]
            if st.kind == "lut":
                j_n, co = st.mask.shape
                idxs = []
                for cols in sites:                      # (TB, J*co) per site
                    code = jnp.concatenate(
                        [jnp.broadcast_to(c, (tb, co)) for c in cols], axis=1)
                    if st.in_shift is not None:
                        code = _shift_round(code, consts[ent["in_shift"]][...])
                    idxs.append(code & consts[ent["mask"]][...])
                per_site = []
                for val in _lookup(idxs, consts[ent["table"]], dt):
                    acc = val[:, :co]                   # Σ_j -> (TB, co)
                    for j in range(1, j_n):
                        acc = acc + val[:, j * co:(j + 1) * co]
                    per_site.append(acc)
            else:
                coef = np.asarray(st.coef)
                per_site = []
                for s, cols in enumerate(sites):
                    acc = jnp.zeros((tb, 1), dt)
                    for c, k in zip(cols, coef[s]):
                        if k:
                            acc = acc + c * jnp.asarray(k, dt)
                    per_site.append(acc)
            acc = jnp.concatenate(per_site, axis=1) \
                if len(per_site) > 1 else per_site[0]   # (TB, S*co)
            acc = acc + consts[ent["bias"]][...]
            for m, epi in enumerate(st.epilogue):
                p = consts[ent[f"epi{m}"]][...]
                if epi.op == "REQUANT":
                    res = _requant_cols(acc, p[0:1], p[1:2], p[2:3] != 0,
                                        epi.mode)
                    if bool(np.all(np.asarray(epi.params)[..., 3] != 0)):
                        acc = res                       # statically all-apply
                    else:
                        acc = jnp.where(p[3:4] != 0, res, acc)
                else:                                   # CMUL
                    acc = acc * p
            v = acc
        outs = [_column(v, int(c), v.shape[1]) for c in packed.out_cols]
        out_ref[...] = jnp.concatenate(outs, axis=1) if len(outs) > 1 \
            else outs[0]
    return kernel


def _max_tile(packed: PackedStages) -> int:
    """Largest power-of-two batch tile whose intermediates fit
    :data:`DEF_TILE_VMEM` (never below 8 rows, one sublane tile)."""
    lanes = lambda n: -(-int(n) // 128) * 128
    width = max((st.n_sites * lanes(st.mask.size if st.kind == "lut"
                                    else st.c_out)
                 for st in packed.stages), default=128)
    rows = DEF_TILE_VMEM // (_LIVE_BLOCKS * 4 * width)
    return max(8, 1 << (max(rows, 1).bit_length() - 1))


def _full_spec(shape):
    nd = len(shape)
    return pl.BlockSpec(shape, lambda i, _nd=nd: (0,) * _nd)


def _next_pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


def pallas_runner(packed: PackedStages, dtype, mesh=None, *,
                  block_batch: Optional[int] = None,
                  interpret: Optional[bool] = None):
    """Close a :class:`PackedStages` over device constants -> runner fn.

    Returns ``run(x: (B, n_cols0) cdtype) -> (B, n_outputs)``, the
    single-``pallas_call`` chain.  ``interpret=None`` compiles the kernel
    through Mosaic on a TPU and interprets it on any other backend, so the
    same kernel logic runs everywhere and a Mosaic refusal is an error on
    the chip.  On a multi-device ``mesh`` the call runs under ``shard_map``,
    one batch shard per device over the mesh's DP axes (XLA cannot
    partition a ``pallas_call`` itself).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    bb = int(block_batch or DEF_BLOCK_BATCH)
    if bb < 1:
        raise ValueError(f"block_batch must be >= 1, got {bb}")
    consts_np, entries = _const_arrays(packed, dtype)
    consts = [jnp.asarray(a) for a in consts_np]
    const_specs = [_full_spec(a.shape) for a in consts_np]
    kernel = _make_kernel(packed, entries)
    max_tile = _max_tile(packed)
    n_in, n_out = packed.n_cols0, len(packed.out_cols)

    def call(x):
        b = x.shape[0]
        # small scheduler buckets shrink the tile instead of padding to it
        tb = min(bb, max_tile, _next_pow2(b))
        pb = -b % tb
        xp = jnp.pad(x, ((0, pb), (0, 0))) if pb else x
        out = pl.pallas_call(
            kernel,
            grid=((b + pb) // tb,),
            in_specs=[pl.BlockSpec((tb, n_in), lambda i: (i, 0)),
                      *const_specs],
            out_specs=pl.BlockSpec((tb, n_out), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((b + pb, n_out), xp.dtype),
            interpret=interpret,
        )(xp, *consts)
        return out[:b] if pb else out

    if mesh is None or mesh.devices.size == 1:
        return call

    from jax.sharding import PartitionSpec as P

    from repro.parallel.sharding import batch_axes
    axes = batch_axes(mesh)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    spec = P(axes, None)
    sharded = jax.shard_map(call, mesh=mesh, in_specs=spec, out_specs=spec,
                            check_vma=False)

    def run(x):
        b = x.shape[0]
        pb = -b % n_shards               # every shard gets whole rows
        out = sharded(jnp.pad(x, ((0, pb), (0, 0))) if pb else x)
        return out[:b] if pb else out
    return run
