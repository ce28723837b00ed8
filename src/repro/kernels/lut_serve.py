"""Accelerator-resident integer LUT serving engine.

``core/dais.py`` interprets a compiled :class:`DaisProgram` one scalar
instruction at a time in numpy — a verification oracle, not a runtime: at
batch 1024 a small two-layer model already spends milliseconds in the Python
dispatch loop.  This module lowers the same program onto the accelerator as
a short chain of jittable JAX *integer* ops, so the artifact we verify is
also the artifact we serve.

Lowering strategy
-----------------
Two paths, picked automatically (``ServeEngine.path`` reports which ran;
a fallback to the generic path logs its reason and records it on
``ServeEngine.fuse_reason``):

1. **Fused per-layer path** (chains of per-site segments from the graph
   frontend ``core/lower.py`` — LUT-Dense stacks, LUT/HGQ convs, hybrid
   models, window accumulation): every layer becomes one
   :class:`FusedStage`.  The layer's tables are composed **once** and
   shared by all spatial sites — a "lut" layer keeps its
   :class:`~repro.core.tables.LayerTables` and runs as per-site gather →
   requant → batched table gather → Σ; an "hgq" layer's per-cell
   REQUANT → CMUL → align chains are enumerated over all input codes into
   an equivalent table (relu folds into a vectorized epilogue); window
   sums and standalone relus become table-free gather/sum stages.  The op
   count scales with model *depth*, not instruction count — the ≥10× over
   the numpy interpreter in ``benchmarks/serve_bench.py``.

2. **Generic group path** (anything the composer rejects — non-chain
   dataflow, un-enumerable operand widths, exotic instruction shapes):
   ``DaisProgram.schedule()`` levelizes the SSA program and batches mutually
   independent same-op instructions into :class:`~repro.core.dais.OpGroup`\\ s.
   Each group becomes a handful of array ops over ``(B, n_columns)`` values:

* ``LLUT``    — one batched table gather: the group's truth tables are packed
  into a ``(n, E_max)`` matrix and every column indexes its row with the WRAP
  two's-complement index (``code mod 2**m`` — the contract documented on
  :class:`repro.core.tables.LayerTables`),
* ``REQUANT`` — vectorized shift / round-half-to-even / clamp-or-wrap, the
  integer-exact port of ``core.dais._requant``,
* ``ADD/SUB/CMUL/CONST`` — exact int32/int64 arithmetic with the operand
  alignment shifts precomputed by the scheduler.

  Each group's result is a ``(B, n_group)`` array; argument gathers are
  column selections from the (few) source groups a consumer references.
  All table/shift/clamp constants are closed over as device arrays, so
  ``jax.jit`` sees a flat integer dataflow graph whose op count scales with
  program *depth*, not with instruction count.

Bit-exactness
-------------
The engine is bit-exact against ``DaisProgram.run`` by construction (same
integer ops, same rounding), and :func:`verify_engine` is the gate that
proves it on random plus exhaustive-small inputs — ``launch/serve.py
--engine tables`` refuses to serve unless the gate passes.

Values are int32 when the static range analysis (``core/analysis.py``)
proves every value the engine materializes fits 30 bits — the proven
:func:`engine_width` bound, falling back to the conservative
``DaisProgram.required_width()`` when analysis is unavailable — else int64,
which requires ``JAX_ENABLE_X64=1`` since the engine must keep more than
32 bits of state.  The same analysis supplies per-stage ``live`` entry
masks that the Pallas packer uses to narrow table lanes (``docs/ir.md``).
"""

from __future__ import annotations

import dataclasses
import logging
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dais import DaisProgram, OpGroup, _requant
from repro.core.tables import LayerTables

logger = logging.getLogger(__name__)

# int32 holds any value chain whose declared register width is <= 30 bits:
# REQUANT's 2**width span and the wrap offset ``code - lo`` both stay under
# 2**31 (see _requant_cols); wider programs need the int64 path.
_INT32_MAX_WIDTH = 30


def _x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)


def _pick_dtype(max_width: int):
    if max_width <= _INT32_MAX_WIDTH:
        return jnp.int32
    if not _x64_enabled():
        raise ValueError(
            f"program has {max_width}-bit registers; the int64 engine needs "
            f"JAX_ENABLE_X64=1 (int32 covers widths <= {_INT32_MAX_WIDTH})")
    return jnp.int64


def _check_dtype(dtype, max_width: int) -> None:
    """Reject an explicitly requested dtype that the program overflows.

    Two silent-wrap holes closed here: asking for int32 on a program whose
    transients need more than :data:`_INT32_MAX_WIDTH` bits, and asking for
    int64 while ``JAX_ENABLE_X64`` is off — jax then *silently* downgrades
    every array to int32, which wraps identically badly.
    """
    if max_width <= _INT32_MAX_WIDTH:
        return
    with warnings.catch_warnings():
        # jax's own "requested dtype int64 ... truncated" chatter — our
        # ValueError below is the one actionable signal
        warnings.simplefilter("ignore")
        actual = jnp.asarray(0, dtype).dtype  # what arrays will really get
    if actual != jnp.dtype(jnp.int64):
        hint = ("set JAX_ENABLE_X64=1 so int64 is honored"
                if not _x64_enabled() else "pass dtype=None or jnp.int64")
        raise ValueError(
            f"program has {max_width}-bit registers/transients but the "
            f"requested engine dtype resolves to {np.dtype(actual).name} "
            f"(covers <= {_INT32_MAX_WIDTH} bits) — values would "
            f"overflow-wrap; {hint}")


def engine_width(prog: DaisProgram) -> int:
    """Width bound the engine dtype is sized from.

    The proven :meth:`~repro.core.analysis.ValueRanges.engine_width` of the
    interval analysis when it succeeds — per-register ranges plus the
    structural constants (clamp grids, shift factors, full table rows) a
    backend materializes — else the conservative
    ``DaisProgram.required_width()``.  Never larger than required_width, so
    replacing the old ``required_width() <= 30`` cliff with this bound only
    ever *admits* programs to int32 (``_check_dtype`` still rejects on
    proof when the bound genuinely exceeds the dtype).
    """
    try:
        from repro.core.analysis import analyze_ranges
        return analyze_ranges(prog).engine_width()
    except Exception as e:            # malformed / unanalyzable: stay sound
        logger.debug("range analysis unavailable (%s); "
                     "falling back to required_width", e)
        return prog.required_width()


class EnginePathWarning(UserWarning):
    """A preferred engine lowering was unavailable and compile fell back.

    Emitted by :func:`compile_program` at compile time (in addition to the
    log line and ``ServeEngine.fuse_reason``) so a perf regression cannot
    hide as a quiet path downgrade; ``launch/serve.py --require-fused`` /
    ``--require-pallas`` turn the same condition into a hard failure.
    """


# --------------------------------------------------------------------------- #
# vectorized integer requant (port of core.dais._requant, column-parallel)
# --------------------------------------------------------------------------- #
def _shift_round(v, shift):
    """``v * 2**shift`` on integer codes, round-half-to-even on dropped bits.

    The single jnp implementation of the grid-change rounding of
    ``core.dais._requant`` — shared by the generic REQUANT lowering and the
    per-layer ``lower_tables`` path so the trickiest bit-exact block exists
    once.  ``shift`` broadcasts against ``v`` and may mix signs.
    """
    one = jnp.ones((), v.dtype)
    up = v << jnp.maximum(shift, 0)
    s = jnp.maximum(-shift, 0)
    floor = v >> s
    rem = v - (floor << s)
    half = (one << jnp.maximum(s, 1)) >> 1
    down = jnp.where(rem > half, floor + 1,
                     jnp.where(rem < half, floor, floor + (floor & 1)))
    return jnp.where(shift >= 0, up, down)


def _requant_cols(v, shift, width, signed, mode: str):
    """Re-quantize columns of ``v`` (B, n) onto new grids, bit-exactly.

    ``shift``/``width``/``signed`` are (n,) per-column arrays; ``mode`` is
    the group-wide overflow mode.  Matches ``core.dais._requant`` including
    round-half-to-even on dropped bits.
    """
    one = jnp.ones((), v.dtype)
    code = _shift_round(v, shift)

    n_codes = one << jnp.maximum(width, 0)
    lo = jnp.where(signed, -(n_codes >> 1), jnp.zeros_like(n_codes))
    hi = lo + n_codes - 1
    if mode == "SAT":
        out = jnp.clip(code, lo, hi)
    else:  # WRAP: grids are powers of two, so mod is a two's-complement mask
        out = lo + ((code - lo) & (n_codes - 1))
    return jnp.where(width > 0, out, jnp.zeros_like(out))


# --------------------------------------------------------------------------- #
# program engine
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class ServeEngine:
    """A compiled, jitted integer runtime for one :class:`DaisProgram`."""

    n_inputs: int
    n_outputs: int
    n_instrs: int
    n_groups: int               # op groups (generic) or layer stages (fused)
    dtype: object
    fused: bool                 # True: pre-composed per-layer table path
    path: str                   # "pallas" | "fused" | "generic"
    fuse_reason: str            # downgrade reason(s); "" when the preferred
                                # path ran
    input_f: List[int]
    input_signed: List[bool]
    input_widths: np.ndarray    # (n_inputs,) physical code widths
    output_f: List[int]
    mesh: object                # Mesh | None — request batches shard over DP
    _runner: Callable
    n_launches: int = 0         # kernel launches per inference (pallas: 1;
                                # fused/generic: one per stage/group)
    packed_table_bytes: int = 0  # lane-packed table bytes ("pallas" only)

    def run(self, x_codes) -> jax.Array:
        """(B, n_inputs) integer codes -> (B, n_outputs) integer codes.

        Same contract as ``DaisProgram.run`` (grids ``input_f`` in,
        ``output_f`` out), executed on the default accelerator.
        """
        x = jnp.asarray(x_codes, self.dtype)
        if x.ndim == 1:
            x = x[None]
        # single-device meshes make shard_batch a pure no-op placement, but
        # the host-side device_put still costs ~ms per call — material on the
        # micro-batching serving path, so skip it
        if self.mesh is not None and self.mesh.devices.size > 1:
            from repro.parallel.sharding import shard_batch
            x = shard_batch(x, self.mesh)
        return self._runner(x)

    def run_float(self, x) -> np.ndarray:
        """Convenience mirror of ``DaisProgram.run_float``."""
        x = np.asarray(x, np.float64)
        codes = np.round(x * np.exp2(np.asarray(self.input_f, np.float64)))
        out = np.asarray(jax.device_get(self.run(codes.astype(np.int64))),
                         np.float64)
        return out * np.exp2(-np.asarray(self.output_f, np.float64))

    def clone(self) -> "ServeEngine":
        """A replica-local handle sharing this engine's compiled runner.

        jitted JAX callables are thread-safe and share one trace cache, so
        a clone costs nothing to make and nothing extra to warm — but it
        gives each serving-tier replica its own dataclass instance (own
        identity, own future mutable counters) instead of N threads
        aliasing one handle.  Used by ``repro.serve.tier.ServeTier``.
        """
        return dataclasses.replace(self)

    def warm(self, batch_sizes) -> List[int]:
        """Populate the jit cache for every batch size in ``batch_sizes``.

        jax.jit retraces per input shape, so the first request batch of each
        size would otherwise pay a trace+compile on the serving path.  The
        micro-batching scheduler (``repro/serve/scheduler.py``) pads every
        flush to a power-of-two bucket and calls this at startup with the
        bucket ladder, making steady-state latency trace-free.  Runs all-zero
        codes (always in range); returns the sizes warmed.
        """
        warmed = []
        for b in batch_sizes:
            zeros = np.zeros((int(b), self.n_inputs), np.int64)
            jax.block_until_ready(self.run(zeros))
            warmed.append(int(b))
        return warmed


def compile_program(prog: DaisProgram, *, mesh=None,
                    dtype: Optional[object] = None,
                    fuse_layers: bool = True,
                    engine: Optional[str] = None,
                    stages: Optional["FusedStages"] = None,
                    packed: Optional[object] = None,
                    jit: bool = True,
                    block_batch: Optional[int] = None,
                    narrow: bool = True) -> ServeEngine:
    """Lower a DAIS program to a jitted accelerator engine.

    When the program is a closed chain of "lut" segments (the
    ``compile_sequential`` metadata on ``prog.segments``), each layer's
    REQUANT → LLUT → align → Σ block is pre-composed at compile time into a
    single per-cell table on the incoming register grids, so a layer
    executes as mask → batched gather → sum (three array ops).  Any other
    program shape falls back to the generic levelized :class:`OpGroup`
    lowering — same bit-exact semantics, more ops.  ``fuse_layers=False``
    forces the generic path.

    ``stages``: optional pre-composed :class:`FusedStages` (e.g. loaded from
    a compiled-artifact bundle) — skips the table-composition pass entirely,
    which is the cold-start cost ``launch/serve.py --artifact`` avoids.

    ``engine``: preferred lowering — ``"pallas"`` (the single-launch
    bit-packed mega-kernel of ``kernels/lut_serve_pallas.py``),
    ``"fused"`` (per-stage jitted JAX ops; the default), or ``"groups"``
    (force the generic levelized runner).  Unavailable preferences degrade
    ``pallas -> fused -> generic``; ``packed`` optionally supplies a
    pre-packed :class:`~repro.kernels.lut_serve_pallas.PackedStages` (from
    a v3 artifact bundle), and ``block_batch`` passes through to the
    Pallas runner.  ``fuse_layers=False`` is the legacy
    spelling of ``engine="groups"``.

    ``mesh``: optional ``jax.sharding.Mesh`` — the batch axis of inputs and
    register values is sharded over its DP axes via
    ``parallel.sharding.constrain`` (the program itself is replicated: it is
    weights, i.e. a few KB of tables and shift constants).

    The chosen lowering is recorded on ``ServeEngine.path`` ("pallas" /
    "fused" / "generic"); a fall-back from a preferred path is never
    silent — every downgrade raises :class:`EnginePathWarning` at compile
    time, is logged, and is kept on ``ServeEngine.fuse_reason`` so tests
    and benchmarks can assert which path ran and why.

    ``narrow``: run the static interval analysis (``core/analysis.py``) to
    (a) size the engine dtype from the proven :func:`engine_width` bound
    instead of the conservative ``required_width()``, and (b) hand the
    Pallas packer per-stage ``live`` entry masks so it can shrink table
    lanes to the proven value ranges.  ``narrow=False`` restores the
    legacy required-width behavior (benchmarks use it as the baseline).
    """
    want = engine if engine is not None else \
        ("fused" if fuse_layers else "groups")
    if want not in ("pallas", "fused", "groups"):
        raise ValueError(
            f"unknown engine {want!r} (choices: pallas, fused, groups)")
    ranges = None
    if narrow and stages is None:
        try:
            from repro.core.analysis import analyze_ranges
            ranges = analyze_ranges(prog)
        except Exception as e:        # unanalyzable: required_width is sound
            logger.debug("range analysis unavailable (%s); "
                         "falling back to required_width", e)
    # engine_width/required_width cover transient pre-clamp REQUANT /
    # pre-add align values, which can exceed every declared register width
    width_bound = (ranges.engine_width() if ranges is not None
                   else prog.required_width())
    if dtype is None:
        dtype = _pick_dtype(width_bound)
    else:
        _check_dtype(dtype, width_bound)

    in_instrs = [ins for ins in prog.instrs if ins.op == "IN"]
    input_widths = np.asarray([ins.reg.width for ins in in_instrs], np.int64)

    run, n_groups, path = None, 0, "generic"
    n_launches, packed_bytes = 0, 0
    downgrades: List[str] = []
    reason = ""
    if want in ("pallas", "fused") and stages is None:
        stages, reason = compose_fused_stages(prog, dtype, ranges=ranges)
    if want == "pallas":
        if stages is None:
            downgrades.append(f"pallas (and fused) unavailable: {reason}")
        else:
            from repro.kernels import lut_serve_pallas as _pallas
            try:
                if packed is None:
                    packed = _pallas.pack_stages(stages, dtype)
                run = _pallas.pallas_runner(packed, dtype, mesh,
                                            block_batch=block_batch)
                path, n_groups = "pallas", packed.n_stages()
                n_launches, packed_bytes = 1, packed.table_bytes()
            except _pallas.PackError as e:
                downgrades.append(f"pallas unavailable: {e}")
    if run is None and want in ("pallas", "fused"):
        if stages is not None:
            run, path = _fused_runner(stages, dtype, mesh), "fused"
            n_groups = n_launches = stages.n_stages()
        elif want == "fused":
            downgrades.append(f"fused unavailable: {reason}")
    if run is None:
        run, n_groups = _group_runner(prog, dtype, mesh)
        path, n_launches = "generic", n_groups
    if want == "groups" and not fuse_layers and engine is None:
        # legacy spelling: keep the documented fuse_reason wording
        downgrades = ["fused path disabled (fuse_layers=False)"]
    elif downgrades:
        msg = (f"engine path downgraded to {path!r}: "
               + "; ".join(downgrades))
        warnings.warn(EnginePathWarning(msg), stacklevel=2)
        logger.warning("%s", msg)

    return ServeEngine(
        n_inputs=len(prog.input_f), n_outputs=len(prog.outputs),
        n_instrs=prog.n_instrs(), n_groups=n_groups, dtype=dtype,
        fused=path in ("fused", "pallas"), path=path,
        fuse_reason="; ".join(downgrades),
        input_f=list(prog.input_f), input_signed=list(prog.input_signed),
        input_widths=input_widths, output_f=list(prog.output_f),
        mesh=mesh, _runner=jax.jit(run) if jit else run,
        n_launches=n_launches, packed_table_bytes=packed_bytes)


def _group_runner(prog: DaisProgram, dtype, mesh):
    """Generic lowering: one vectorized op bundle per scheduled OpGroup.

    Each group's result stays its own ``(B, n_group)`` array; a consuming
    group gathers its arguments from the concatenation of just the source
    groups it actually references (usually one or two — the level structure
    keeps fan-in local), so there is no global register matrix to recopy.
    """
    groups = prog.schedule()
    group_of = np.full(len(prog.instrs), -1, np.int64)
    col_in_group = np.full(len(prog.instrs), -1, np.int64)
    for gi, g in enumerate(groups):
        for c, r in enumerate(g.regs):
            group_of[r] = gi
            col_in_group[r] = c
    sizes = [len(g.regs) for g in groups]

    def locate(regs):
        """Source-group set + local columns of ``regs`` within their concat."""
        srcs = sorted({int(group_of[r]) for r in regs})
        off = {}
        acc = 0
        for s in srcs:
            off[s] = acc
            acc += sizes[s]
        cols = np.asarray([off[int(group_of[r])] + int(col_in_group[r])
                           for r in regs], np.int64)
        return srcs, cols

    prepared = [_prepare_group(prog, g, locate, dtype) for g in groups]
    out_srcs, out_cols = locate(prog.outputs)

    def _assemble(results, srcs):
        if len(srcs) == 1:
            return results[srcs[0]]
        return jnp.concatenate([results[s] for s in srcs], 1)

    def _run(x):
        if mesh is not None:
            from repro.parallel.sharding import constrain
            x = constrain(x, mesh, "batch", None)
        results = []
        for srcs, ex in prepared:
            base = _assemble(results, srcs) if srcs else None
            results.append(ex(base, x))
        return _assemble(results, out_srcs)[:, out_cols]
    return _run, len(groups)


def _prepare_group(prog: DaisProgram, g: OpGroup, locate, dtype):
    """Close a single OpGroup over its device constants.

    Returns ``(srcs, ex)``: ``srcs`` are the indices of the earlier groups
    this one reads from, and ``ex(base, x) -> (B, n)`` computes the group
    from ``base`` — the (B, Σ sizes) concatenation of those groups' results
    — and the (B, n_inputs) input codes ``x``.
    """
    a = g.args
    dev = lambda arr: jnp.asarray(np.asarray(arr), dtype)

    if g.op == "IN":
        ks = np.asarray(a["k"], np.int64)
        return [], lambda base, x: x[:, ks]

    if g.op == "CONST":
        cs = dev(a["c"])
        return [], lambda base, x: jnp.broadcast_to(
            cs[None], (x.shape[0], len(cs)))

    if g.op == "REQUANT":
        srcs, src = locate(a["src"])
        shift = dev(a["f"] - a["src_f"])
        width = dev(a["f"] + a["i"] + a["signed"])
        signed = jnp.asarray(a["signed"] != 0)
        mode = g.mode
        return srcs, lambda base, x: _requant_cols(base[:, src], shift, width,
                                                   signed, mode)

    if g.op == "LLUT":
        srcs, src = locate(a["src"])
        n = len(src)
        sizes_np = np.empty(n, np.int64)
        rows = []
        for col in range(n):
            t = prog.tables[int(a["layer"][col])]
            j, i = int(a["j"][col]), int(a["i"][col])
            sizes_np[col] = t.entry_sizes()[j, i]
            rows.append(np.asarray(t.codes[j, i], np.int64))
        e_max = max(int(s) for s in sizes_np)
        table = np.zeros((n, e_max), np.int64)
        for col, row in enumerate(rows):
            table[col, :min(len(row), e_max)] = row[:e_max]
        table_d = dev(table)
        masks = dev(sizes_np - 1)
        rng = jnp.arange(n)[None, :]

        def ex(base, x):
            # WRAP contract (tables.py): idx = code mod 2**m == code & (2**m-1)
            idx = base[:, src] & masks
            return table_d[rng, idx]
        return srcs, ex

    if g.op == "CMUL":
        srcs, src = locate(a["src"])
        codes = dev(a["code"])
        return srcs, lambda base, x: base[:, src] * codes[None]

    # ADD / SUB — locate both operand sets against one shared base
    n = len(a["a"])
    srcs, cols = locate(list(a["a"]) + list(a["b"]))
    ca, cb = cols[:n], cols[n:]
    sa, sb = dev(a["shift_a"]), dev(a["shift_b"])
    sign = 1 if g.op == "ADD" else -1

    def ex(base, x):
        return (base[:, ca] << sa) + sign * (base[:, cb] << sb)
    return srcs, ex


# --------------------------------------------------------------------------- #
# fused per-layer path: tables composed once per layer, gathered per site
# --------------------------------------------------------------------------- #
# Caps on what the composer will enumerate: one stage's table may not exceed
# _MAX_COMPOSED_ELEMS entries, and a single operand chain is only enumerated
# when its input register is at most _MAX_ENUM_WIDTH bits wide.
_MAX_COMPOSED_ELEMS = 1 << 24
_MAX_ENUM_WIDTH = 20


class _ComposeError(Exception):
    """Raised inside the composer; the message is the fall-back reason."""


@dataclasses.dataclass
class EpiOp:
    """One vectorized per-channel epilogue op applied after a stage's Σ.

    ``REQUANT``: ``params`` is ``(S, co, 4)`` = (grid shift, width, signed,
    apply) with the overflow ``mode`` shared — ``apply == 0`` marks
    channels whose output folded entirely into their term/bias (no
    epilogue instruction), which pass through untouched; ``CMUL``:
    ``params`` is ``(S, co)`` constant codes (1 = pass-through).
    """

    op: str                      # "REQUANT" | "CMUL"
    mode: str                    # REQUANT overflow mode; "" for CMUL
    params: np.ndarray


@dataclasses.dataclass
class FusedStage:
    """One layer of the fused runner, shared tables + per-site gathers.

    ``gather`` is ``(S, J)``: for each of the layer's ``S`` spatial sites,
    the ``J`` columns of the incoming flat value matrix it reads (the value
    ``n_cols`` addresses an implicit all-zero column — the im2col zero
    pad).  Kind "lut" then computes, per cell ``(j, i)``,
    ``table[j, i, mask & shift_round(v)] << out_shift`` and sums over
    ``j`` — the table is stored **once** and indexed by every site, which
    is the whole point of the shared-table lowering.  Kind "sum" is the
    table-free variant (window accumulation, standalone relu):
    ``Σ_j sign * (v << shift)``.  Both add ``bias`` and then apply the
    ``epilogue`` ops (e.g. an HGQ layer's relu clamp).  The stage output is
    ``(B, S, co)`` reshaped to the next stage's flat ``(B, S*co)``.
    """

    kind: str                    # "lut" | "sum"
    gather: np.ndarray           # (S, J) int64; == n_cols -> zero column
    n_cols: int                  # incoming flat width
    bias: np.ndarray             # (S, co) int64
    epilogue: List[EpiOp] = dataclasses.field(default_factory=list)
    # kind "lut"
    in_shift: Optional[np.ndarray] = None   # (J, co) grid shifts
    mask: Optional[np.ndarray] = None       # (J, co) index masks
    table: Optional[np.ndarray] = None      # (J, co, E) int64, site-shared
    out_shift: Optional[np.ndarray] = None  # (J, co) alignment shifts
    # kind "sum"
    shifts: Optional[np.ndarray] = None     # (S, J) alignment shifts
    signs: Optional[np.ndarray] = None      # (S, J) in {-1, 0, +1}
    # kind "lut", optional: (J, co, E) bool — entries the range analysis
    # proves reachable.  Compile-time metadata only (the Pallas packer
    # zeroes dead entries before lane selection); NOT part of the wire
    # format, so bundles reload without it and simply skip narrowing.
    live: Optional[np.ndarray] = None

    @property
    def n_sites(self) -> int:
        return self.gather.shape[0]

    @property
    def c_out(self) -> int:
        return self.bias.shape[1]


@dataclasses.dataclass
class FusedStages:
    """The compile-time product of the fused path, as plain data.

    One :class:`FusedStage` per graph layer plus the output column
    selection.  This is everything the fused runner closes over, split out
    so the compiled-artifact cache (``repro/serve/artifact.py``) can
    persist it and :func:`compile_program` can rebuild the engine from a
    bundle without re-running the composition pass.
    """

    stages: List[FusedStage]
    out_cols: np.ndarray         # (n_outputs,) columns of the final stage

    def n_stages(self) -> int:
        return len(self.stages)

    def n_table_entries(self) -> int:
        """Total stored truth-table entries across the "lut" stages.

        Shrinks under the dead-cell elimination pass (``repro.core.opt``)
        when pruned rows are sliced out of the shared tables;
        ``benchmarks/serve_bench.py`` records it on the DCE row.
        """
        return int(sum(st.table.size for st in self.stages
                       if st.table is not None))


# ---------------------------------------------------------------- composer
def _reg_fmt(prog: DaisProgram, r: int):
    reg = prog.instrs[r].reg
    return (reg.f, max(reg.width, 1), reg.signed)


_MIXED_FMT = "mixed"


def _stage_gather(prog: DaisProgram, segs, colmap, n_cols):
    """Per-site column gather + per-position incoming formats.

    Registers absent from ``colmap`` must be zero CONSTs (the im2col pads)
    and map to the implicit zero column ``n_cols``.  A position whose
    format differs across sites reports the :data:`_MIXED_FMT` sentinel —
    only table-building stage kinds need uniform formats (the
    chain-as-epilogue and table-free sum kinds don't), so the decision to
    reject is theirs (:func:`_stage_fmts`).
    """
    n_sites, j_n = len(segs), len(segs[0].in_regs)
    gather = np.full((n_sites, j_n), n_cols, np.int64)
    fmts: List[Optional[tuple]] = [None] * j_n
    pad_fmts: List[Optional[tuple]] = [None] * j_n
    for s, seg in enumerate(segs):
        if len(seg.in_regs) != j_n:
            raise _ComposeError("sites disagree on patch size")
        for j, r in enumerate(seg.in_regs):
            if r in colmap:
                gather[s, j] = colmap[r]
                fmt = _reg_fmt(prog, r)
                if fmts[j] is None:
                    fmts[j] = fmt
                elif fmts[j] != fmt:
                    fmts[j] = _MIXED_FMT
            else:
                ins = prog.instrs[r]
                if ins.op != "CONST" or ins.args[0] != 0:
                    raise _ComposeError(
                        f"input register r{r} is neither a previous-stage "
                        f"output nor a zero pad")
                pad_fmts[j] = _reg_fmt(prog, r)
    fmts = [f if f is not None else p for f, p in zip(fmts, pad_fmts)]
    return gather, fmts


def _stage_fmts(fmts) -> List[tuple]:
    """Uniform per-position formats, or a compose error for mixed ones."""
    for j, f in enumerate(fmts):
        if f == _MIXED_FMT:
            raise _ComposeError(
                f"position {j} has site-dependent register formats")
    return fmts


def _compose_lut_stage(prog: DaisProgram, segs, gather, fmts) -> FusedStage:
    """A "lut" layer: keep the shared LayerTables, requant + gather per site.

    The REQUANT → LLUT → align-CMUL chain of every cell is a pure function
    of one incoming code, evaluated at run time as shift-round → mask →
    table gather → align shift (the WRAP contract of
    ``core.tables.LayerTables``), so arbitrarily wide incoming registers
    never need enumerating and the table is exactly ``t.codes`` — stored
    once, indexed by all ``S`` sites.
    """
    t = prog.tables.get(segs[0].layer_id)
    if t is None:
        raise _ComposeError(f"layer {segs[0].layer_id} has no tables")
    ci, co = t.c_in, t.c_out
    if gather.shape[1] != ci or any(len(s.out_regs) != co for s in segs):
        raise _ComposeError("segment register counts don't match its tables")
    if int(np.asarray(t.codes).size) > _MAX_COMPOSED_ELEMS:
        raise _ComposeError(f"table too large ({t.codes.size} entries)")
    in_f = np.asarray([f for f, _w, _s in _stage_fmts(fmts)], np.int64)
    in_shift, mask, out_shift = t.gather_params(in_f)
    return FusedStage(
        kind="lut", gather=gather, n_cols=0,
        bias=np.zeros((len(segs), co), np.int64),
        in_shift=in_shift, mask=mask,
        table=np.asarray(t.codes, np.int64), out_shift=out_shift)


def _unary_chain(prog: DaisProgram, out_reg: int, symbols) -> Tuple[List[int], int]:
    """Longest REQUANT/CMUL/LLUT chain ending at ``out_reg``; returns the
    chain (outermost first) and the register it bottoms out on."""
    chain, r = [], out_reg
    while r not in symbols and prog.instrs[r].op in ("REQUANT", "CMUL", "LLUT"):
        chain.append(r)
        r = prog.instrs[r].args[0]
    return chain, r


def _collect_terms(prog: DaisProgram, root: int, symbols):
    """Decompose the ADD/SUB tree below ``root`` into univariate terms.

    Returns ``(terms, consts)``: each term is ``(j, sign, shift, chain)``
    — a unary instruction chain (innermost first) on symbol ``j``, shifted
    onto the root grid and signed; each const is ``(value, sign, shift,
    chain)``.  Raises :class:`_ComposeError` on anything else (the segment
    is then not a sum of univariate functions and cannot fuse).
    """
    terms, consts = [], []

    def walk(r, sign, shift, suffix):
        if r in symbols:
            terms.append((symbols[r], sign, shift, list(reversed(suffix))))
            return
        ins = prog.instrs[r]
        if ins.op == "CONST":
            consts.append((int(ins.args[0]), sign, shift, list(reversed(suffix))))
        elif ins.op in ("REQUANT", "CMUL", "LLUT"):
            walk(ins.args[0], sign, shift, suffix + [r])
        elif ins.op in ("ADD", "SUB"):
            if suffix:
                # a unary op below an ADD consumed by another unary chain is
                # fine; an ADD *inside* a unary suffix is not univariate
                raise _ComposeError("ADD nested inside a unary chain")
            ra, rb = ins.args
            fa, fb = prog.instrs[ra].reg.f, prog.instrs[rb].reg.f
            f = max(fa, fb)
            walk(ra, sign, shift + (f - fa), [])
            walk(rb, sign * (-1 if ins.op == "SUB" else 1),
                 shift + (f - fb), [])
        else:
            raise _ComposeError(f"op {ins.op} inside a segment body")

    ins = prog.instrs[root]
    if ins.op in ("ADD", "SUB"):
        ra, rb = ins.args
        fa, fb = prog.instrs[ra].reg.f, prog.instrs[rb].reg.f
        f = max(fa, fb)
        walk(ra, 1, f - fa, [])
        walk(rb, -1 if ins.op == "SUB" else 1, f - fb, [])
    else:
        walk(root, 1, 0, [])
    return terms, consts


def _eval_chain(prog: DaisProgram, chain: List[int], values: np.ndarray) -> np.ndarray:
    """Exactly evaluate a unary instruction chain on integer codes."""
    v = np.asarray(values, np.int64)
    for r in chain:
        ins = prog.instrs[r]
        if ins.op == "REQUANT":
            _src, f, i, signed, mode, src_f = ins.args
            v = _requant(v, src_f, f, i, signed, mode)
        elif ins.op == "CMUL":
            v = v * np.int64(ins.args[1])
        elif ins.op == "LLUT":
            _src, lid, j, i = ins.args
            t = prog.tables[lid]
            m = int(t.in_width[j, i])
            size = 1 << m if m > 0 else 1
            v = t.codes[j, i, np.mod(v, size)]
        else:  # unreachable: _unary_chain/_collect_terms only pass these ops
            raise _ComposeError(f"op {ins.op} in a unary chain")
    return v


def _chain_key(prog: DaisProgram, chain: List[int]) -> tuple:
    """Structural fingerprint of a unary chain (op + non-register args)."""
    return tuple((prog.instrs[r].op,) + tuple(prog.instrs[r].args[1:])
                 for r in chain)


def _decompose_site(prog: DaisProgram, seg):
    """Per-output structure of one site: (epilogue chain, terms, consts)."""
    symbols = {r: j for j, r in enumerate(seg.in_regs)}
    outs = []
    for out_reg in seg.out_regs:
        chain, r = _unary_chain(prog, out_reg, symbols)
        if r in symbols or prog.instrs[r].op == "CONST":
            # pure univariate chain (or folded constant): no epilogue, the
            # whole chain lives in the term/const
            terms, consts = _collect_terms(prog, out_reg, symbols)
            outs.append(([], terms, consts))
        elif prog.instrs[r].op in ("ADD", "SUB"):
            terms, consts = _collect_terms(prog, r, symbols)
            outs.append((list(reversed(chain)), terms, consts))
        else:
            raise _ComposeError(f"op {prog.instrs[r].op} at a segment output")
    return outs


def _epilogue_ops(prog: DaisProgram, per_site_epis, co: int) -> List[EpiOp]:
    """Vectorize per-(site, channel) epilogue chains into shared EpiOps.

    Every channel/site must agree on the op-name sequence; channels whose
    output folded to a constant/pure chain carry ``apply == 0`` and pass
    through untouched (a fake "identity" requant could clamp legal values
    of unsigned registers at the dtype width cap).
    """
    n_sites = len(per_site_epis)
    shapes = {tuple(prog.instrs[r].op for r in epi)
              for site in per_site_epis for epi in site if epi}
    if not shapes:
        return []
    if len(shapes) > 1:
        raise _ComposeError("outputs disagree on epilogue structure")
    ops = next(iter(shapes))
    out: List[EpiOp] = []
    for k, op in enumerate(ops):
        if op == "REQUANT":
            params = np.zeros((n_sites, co, 4), np.int64)
            params[..., 1] = 1            # harmless width for masked channels
            mode = None
            for s, site in enumerate(per_site_epis):
                for i, epi in enumerate(site):
                    if not epi:
                        continue
                    _src, f, ib, signed, m, src_f = prog.instrs[epi[k]].args
                    if mode is None:
                        mode = m
                    elif mode != m:
                        raise _ComposeError("mixed REQUANT modes in epilogue")
                    width = f + ib + (1 if signed else 0)
                    params[s, i] = (f - src_f, width, int(bool(signed)), 1)
            out.append(EpiOp(op="REQUANT", mode=mode or "SAT", params=params))
        elif op == "CMUL":
            params = np.ones((n_sites, co), np.int64)
            for s, site in enumerate(per_site_epis):
                for i, epi in enumerate(site):
                    if epi:
                        params[s, i] = int(prog.instrs[epi[k]].args[1])
            out.append(EpiOp(op="CMUL", mode="", params=params))
        else:
            raise _ComposeError(f"op {op} in an epilogue (not vectorizable)")
    return out


def _chain_only_site(prog: DaisProgram, site) -> Optional[List[int]]:
    """The single REQUANT/CMUL-only chain of a one-output site, or None.

    The shape a standalone relu lowers to: one unshifted positive bare-ish
    term whose unary chain can run *as the epilogue* on the gathered value
    itself — no enumeration, so the operand may be arbitrarily wide.
    """
    epi, terms, consts = site[0]
    if epi or consts or len(terms) != 1:
        return None
    _j, sign, shift, chain = terms[0]
    if (sign != 1 or shift != 0 or not chain
            or any(prog.instrs[r].op not in ("REQUANT", "CMUL")
                   for r in chain)):
        return None
    return chain


def _compose_enum_stage(prog: DaisProgram, segs, gather, fmts) -> FusedStage:
    """An "hgq"/"acc"/"relu" layer: decompose each output into a sum of
    univariate chains, then the cheapest faithful stage: table-free "sum"
    (every term a bare register — window accumulation), chain-as-epilogue
    (standalone relu), or each chain enumerated over its input register's
    code space into a site-shared table ("lut" semantics without
    LayerTables).
    """
    n_sites, j_n = gather.shape
    co = len(segs[0].out_regs)
    if any(len(s.out_regs) != co for s in segs):
        raise _ComposeError("sites disagree on output count")
    sites = [_decompose_site(prog, seg) for seg in segs]
    site0 = sites[0]

    # table-free chain-as-epilogue (standalone relu): per-site chains may
    # differ in params (per-channel grids) — only the op sequence must
    # agree, which _epilogue_ops enforces
    if co == 1 and j_n == 1:
        chains = [_chain_only_site(prog, site) for site in sites]
        if all(c is not None for c in chains):
            return FusedStage(
                kind="sum", gather=gather, n_cols=0,
                bias=np.zeros((n_sites, 1), np.int64),
                epilogue=_epilogue_ops(prog, [[c] for c in chains], co),
                shifts=np.zeros((n_sites, 1), np.int64),
                signs=np.ones((n_sites, 1), np.int64))

    # shared structure check: term chains must be identical across sites
    key0 = [[(j, sign, shift, _chain_key(prog, chain))
             for j, sign, shift, chain in terms]
            for _epi, terms, _consts in site0]
    for s, site in enumerate(sites[1:], start=1):
        key = [[(j, sign, shift, _chain_key(prog, chain))
                for j, sign, shift, chain in terms]
               for _epi, terms, _consts in site]
        if key != key0:
            raise _ComposeError(
                f"site {s} disagrees with site 0 on term structure")

    bias = np.zeros((n_sites, co), np.int64)
    for s, site in enumerate(sites):
        for i, (_epi, _terms, consts) in enumerate(site):
            for value, sign, shift, chain in consts:
                v = int(_eval_chain(prog, chain, np.asarray([value]))[0])
                bias[s, i] += sign * (v << shift)
    epilogue = _epilogue_ops(prog, [[epi for epi, _t, _c in site]
                                    for site in sites], co)

    all_terms = [t for _epi, terms, _c in site0 for t in terms]
    if co == 1 and all(not chain for _j, _sg, _sh, chain in all_terms):
        # table-free: window accumulation / plain aligned sums
        shifts = np.zeros((n_sites, j_n), np.int64)
        signs = np.zeros((n_sites, j_n), np.int64)
        for s, site in enumerate(sites):
            for _epi, terms, _c in site:
                for j, sign, shift, _chain in terms:
                    if signs[s, j]:
                        raise _ComposeError(
                            "register used twice in one table-free sum")
                    signs[s, j], shifts[s, j] = sign, shift
        return FusedStage(kind="sum", gather=gather, n_cols=0, bias=bias,
                          epilogue=epilogue, shifts=shifts, signs=signs)

    # enumerated tables: one (J, co, E) table shared by every site
    widths = [w for _f, w, _s in _stage_fmts(fmts)]
    if max(widths) > _MAX_ENUM_WIDTH:
        raise _ComposeError(
            f"operand register too wide to enumerate "
            f"({max(widths)} > {_MAX_ENUM_WIDTH} bits)")
    e_max = 1 << max(widths)
    if j_n * co * e_max > _MAX_COMPOSED_ELEMS:
        raise _ComposeError(
            f"composed table too large ({j_n * co * e_max} entries)")
    table = np.zeros((j_n, co, e_max), np.int64)
    mask = np.zeros((j_n, co), np.int64)
    codes = []
    for j, (_f, w, signed) in enumerate(fmts):
        e = np.arange(1 << w, dtype=np.int64)
        codes.append(np.where(e >= (1 << w) // 2, e - (1 << w), e)
                     if signed else e)
        mask[j, :] = (1 << w) - 1
    for i, (_epi, terms, _c) in enumerate(site0):
        for j, sign, shift, chain in terms:
            v = _eval_chain(prog, chain, codes[j])
            table[j, i, :len(v)] += sign * (v << shift)
    return FusedStage(kind="lut", gather=gather, n_cols=0, bias=bias,
                      epilogue=epilogue,
                      in_shift=np.zeros((j_n, co), np.int64), mask=mask,
                      table=table,
                      out_shift=np.zeros((j_n, co), np.int64))


def _shift_round_scalar(v: int, shift: int) -> int:
    """Python-int twin of :func:`_shift_round` (monotone in ``v``)."""
    if shift >= 0:
        return v << shift
    from repro.core.analysis import _round_half_even
    return _round_half_even(v, -shift)


def _stage_live(ranges, segs, stage: FusedStage) -> np.ndarray:
    """(J, co, E) bool mask of table entries any site can actually index.

    Per cell ``(j, i)`` the runtime index is
    ``shift_round(v) & mask[j, i]`` for ``v`` the site's incoming register
    value; with the proven ``[lo, hi]`` of that register and the shift
    being monotone, the reachable indices form a wrap-aware window
    (:func:`~repro.core.analysis.index_window`).  Entries outside the
    union of all sites' windows — and entries past each cell's
    ``mask + 1`` grid size — are dead: typically the saturation rows that
    hold the largest-magnitude codes, which is exactly what keeps the
    packed lane dtype wide.
    """
    from repro.core.analysis import index_window
    j_n, co, e_max = stage.table.shape
    live = np.zeros((j_n, co, e_max), bool)
    for seg in segs:
        for j, r in enumerate(seg.in_regs):
            lo, hi = ranges.range(r)
            for i in range(co):
                sh = int(stage.in_shift[j, i])
                size = int(stage.mask[j, i]) + 1
                win = index_window(_shift_round_scalar(lo, sh),
                                   _shift_round_scalar(hi, sh), size)
                live[j, i, :size] |= win
    return live


def compose_fused_stages(prog: DaisProgram, dtype: Optional[object] = None,
                         *, ranges: Optional[object] = None,
                         ) -> Tuple[Optional[FusedStages], str]:
    """Compose a chain of per-site segments into per-layer fused stages.

    Returns ``(stages, "")`` on success, or ``(None, reason)`` when the
    program does not fit the fused pattern — callers then fall back to the
    generic :class:`OpGroup` lowering (same semantics, more ops) and should
    surface ``reason``.

    ``ranges``: optional :class:`~repro.core.analysis.ValueRanges` for
    ``prog`` — each "lut" stage then carries a ``live`` entry mask
    (:func:`_stage_live`) that the Pallas packer uses to narrow lanes.
    """
    if dtype is None:
        try:
            dtype = _pick_dtype(ranges.engine_width() if ranges is not None
                                else engine_width(prog))
        except ValueError as e:
            return None, str(e)
    if not prog.segments:
        return None, "program has no segment metadata"
    groups: List[list] = []
    for seg in prog.segments:
        if groups and groups[-1][0].layer_id == seg.layer_id:
            groups[-1].append(seg)
        else:
            groups.append([seg])
    colmap = {idx: int(ins.args[0]) for idx, ins in enumerate(prog.instrs)
              if ins.op == "IN"}
    n_cols = len(prog.input_f)
    stages: List[FusedStage] = []
    try:
        for segs in groups:
            kinds = {s.kind for s in segs}
            sites = sorted(s.site for s in segs)
            if len(kinds) != 1 or sites != list(range(len(segs))) or \
                    any(s.n_sites != len(segs) for s in segs):
                raise _ComposeError(
                    f"layer {segs[0].layer_id} has inconsistent site metadata")
            gather, fmts = _stage_gather(prog, segs, colmap, n_cols)
            if segs[0].kind == "lut":
                stage = _compose_lut_stage(prog, segs, gather, fmts)
            else:
                stage = _compose_enum_stage(prog, segs, gather, fmts)
            stage.n_cols = n_cols
            if ranges is not None and stage.table is not None:
                stage.live = _stage_live(ranges, segs, stage)
            stages.append(stage)
            colmap = {r: s * stage.c_out + i
                      for s, seg in enumerate(segs)
                      for i, r in enumerate(seg.out_regs)}
            n_cols = len(segs) * stage.c_out
        out_cols = np.asarray([colmap[r] for r in prog.outputs], np.int64)
    except _ComposeError as e:
        return None, str(e)
    except KeyError as e:
        return None, f"non-chain dataflow (register {e} skips a stage)"
    return FusedStages(stages=stages, out_cols=out_cols), ""


# ------------------------------------------------------------------ runner
def _prepare_stage(stage: FusedStage, dtype):
    """Close one FusedStage over device constants -> (B, n_cols) -> (B, S*co)."""
    gather = jnp.asarray(np.asarray(stage.gather, np.int32))
    bias = jnp.asarray(stage.bias, dtype)[None]             # (1, S, co)
    epis = []
    for e in stage.epilogue:
        if e.op == "REQUANT":
            epis.append((e.op, e.mode,
                         jnp.asarray(e.params[..., 0], dtype)[None],
                         jnp.asarray(e.params[..., 1], dtype)[None],
                         jnp.asarray(e.params[..., 2] != 0)[None],
                         jnp.asarray(e.params[..., 3] != 0)[None]))
        else:
            epis.append((e.op, "", jnp.asarray(e.params, dtype)[None],
                         None, None, None))

    if stage.kind == "lut":
        in_shift = jnp.asarray(stage.in_shift, dtype)       # (J, co)
        mask = jnp.asarray(stage.mask, dtype)
        table = jnp.asarray(stage.table, dtype)             # (J, co, E)
        out_shift = jnp.asarray(stage.out_shift, dtype)
        jj = jnp.arange(table.shape[0])[:, None]
        ii = jnp.arange(table.shape[1])[None, :]

        def body(g):                                        # g: (B, S, J)
            code = _shift_round(g[..., None], in_shift)     # (B, S, J, co)
            idx = code & mask
            vals = table[jj, ii, idx] << out_shift
            return vals.sum(axis=2)                         # (B, S, co)
    else:
        shifts = jnp.asarray(stage.shifts, dtype)[None]     # (1, S, J)
        signs = jnp.asarray(stage.signs, dtype)[None]

        def body(g):
            return (signs * (g << shifts)).sum(axis=-1)[..., None]

    def ex(v):
        b = v.shape[0]
        vz = jnp.concatenate([v, jnp.zeros((b, 1), v.dtype)], axis=1)
        acc = body(vz[:, gather]) + bias
        for op, mode, p0, p1, p2, apply in epis:
            if op == "REQUANT":
                acc = jnp.where(apply, _requant_cols(acc, p0, p1, p2, mode),
                                acc)
            else:
                acc = acc * p0
        return acc.reshape(b, -1)
    return ex


def _fused_runner(stages: FusedStages, dtype, mesh):
    """Close a :class:`FusedStages` over device constants -> runner fn."""
    prepared = [_prepare_stage(st, dtype) for st in stages.stages]
    out_cols = np.asarray(stages.out_cols, np.int64)

    def _run(x):
        if mesh is not None:
            from repro.parallel.sharding import constrain
            x = constrain(x, mesh, "batch", None)
        v = x
        for ex in prepared:
            v = ex(v)
        return v[:, out_cols]
    return _run


# --------------------------------------------------------------------------- #
# single-layer engine: jax port of LayerTables.lookup_codes
# --------------------------------------------------------------------------- #
def lower_tables(t: LayerTables, x_f, x_width: int = 16,
                 jit: bool = True) -> Callable:
    """Jitted batched gather evaluating one layer's truth tables.

    Returns ``fn(x_codes) -> out_codes`` bit-exact against
    ``t.lookup_codes(x_codes, x_f)``: (B, C_in) codes on the ``x_f`` grid in,
    (B, C_out) codes on the ``t.common_f_out()`` grid out.  ``x_width`` is
    the physical width of the input codes (bounds the internal dtype).
    """
    ci, co = t.c_in, t.c_out
    # (in_shift, mask, out_shift) incl. the pruned-cell out-shift clamp:
    # one derivation, shared with the fused stage composer
    shift, masks_np, out_shift_np = t.gather_params(x_f)    # (ci, co) each

    width_bound = max(
        int(x_width + max(shift.max(), 0)) + 1,
        int((np.maximum(t.out_width, 1) + out_shift_np).max())
        + int(np.ceil(np.log2(max(ci, 1)))) + 1)
    dtype = _pick_dtype(width_bound)

    codes_d = jnp.asarray(t.codes, dtype)
    sh = jnp.asarray(shift, dtype)[None]                    # (1, ci, co)
    masks = jnp.asarray(masks_np, dtype)[None]
    out_shift = jnp.asarray(out_shift_np, dtype)[None]
    jj = jnp.arange(ci)[:, None]
    ii = jnp.arange(co)[None, :]

    def fn(x_codes):
        v = jnp.asarray(x_codes, dtype)[..., :, None]   # (B, ci, 1)
        # integer round-half-to-even requant onto each cell's f_in grid
        code = _shift_round(v, sh)
        idx = code & masks              # the WRAP contract (grids are 2**m)
        out = codes_d[jj, ii, idx]                          # (B, ci, co)
        return (out << out_shift).sum(axis=-2)
    return jax.jit(fn) if jit else fn


# --------------------------------------------------------------------------- #
# bit-exactness gate
# --------------------------------------------------------------------------- #
def input_code_bounds(prog: DaisProgram):
    """Per-input inclusive (lo, hi) integer code ranges of a program."""
    widths = [ins.reg.width for ins in prog.instrs if ins.op == "IN"]
    lo, hi = [], []
    for w, s in zip(widths, prog.input_signed):
        n = 1 << max(w, 1)
        lo.append(-(n >> 1) if s else 0)
        hi.append((lo[-1] + n - 1))
    return np.asarray(lo, np.int64), np.asarray(hi, np.int64)


def verify_engine(engine: ServeEngine, prog: DaisProgram, *,
                  n_random: int = 1024, seed: int = 0,
                  exhaustive_limit: int = 4096) -> Dict[str, int]:
    """Assert the accelerator engine matches ``DaisProgram.run`` bit-for-bit.

    Checks ``n_random`` uniform random input-code vectors, plus the full
    input cross-product whenever it has at most ``exhaustive_limit`` rows.
    Raises ``AssertionError`` on the first mismatch; returns the row counts
    checked so callers can log the gate.
    """
    lo, hi = input_code_bounds(prog)
    rng = np.random.default_rng(seed)
    batches = [rng.integers(lo, hi + 1, (n_random, len(lo)), dtype=np.int64)]
    sizes = hi - lo + 1
    n_exhaustive = 0
    # log-domain size test: wide input spaces (e.g. a 100-sample 12-bit
    # waveform context) would overflow a plain product
    if np.sum(np.log2(sizes.astype(np.float64))) <= np.log2(exhaustive_limit):
        grid = np.indices(tuple(int(s) for s in sizes))
        batches.append(grid.reshape(len(lo), -1).T + lo[None, :])
        n_exhaustive = batches[-1].shape[0]
    for codes in batches:
        ref = prog.run(codes)
        got = np.asarray(jax.device_get(engine.run(codes)), np.int64)
        np.testing.assert_array_equal(
            got, ref, err_msg="accelerator engine != DAIS interpreter")
    return {"random": n_random, "exhaustive": n_exhaustive,
            "max_width": prog.max_width(), "n_groups": engine.n_groups}
