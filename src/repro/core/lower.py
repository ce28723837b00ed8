"""Graph lowering: compile hybrid LUT/HGQ architectures to DAIS.

The one-shot ``compile_sequential`` frontend could only lower flat
``LUTDense``/``HGQDense`` stacks, so the paper's own hybrid conv models
(HGQ conv frontend → LUT-Conv stack → LUT head → window accumulation)
trained but could never be compiled, served, or emitted as RTL.  This
module replaces it with a general lowering pass over a :class:`ModelGraph`:

* a **per-layer-type registry** (``@register_lowering(LUTDense)`` …) maps
  each node type to the function that emits its DAIS instructions, so new
  layer kinds plug in without touching the driver;
* the graph state between nodes is an integer ndarray of *register ids*
  shaped like the activation tensor (``(T, C)``, ``(H, W, C)``, or
  ``(C,)``), which is what lets structural ops — im2col patch extraction
  with stride/padding, ``Flatten``, ``ReLU``, ``WindowSum`` accumulation —
  be pure index manipulation;
* convolutions lower by **sharing one** :class:`~repro.core.tables.LayerTables`
  **across all spatial sites**: tables are extracted once per layer
  (``extract_tables`` via ``layer.dense``) and every site emits LLUT
  instructions against the same ``layer_id`` — one table set per layer,
  many lookup instances, exactly the FPGA weight-sharing story.  This also
  keeps ``required_width``/EBOPs honest and is what the serving engine's
  fused per-site gather and the Verilog backend's
  one-function-per-shared-table emission rely on.

Every (layer, site) records a :class:`~repro.core.dais.Segment` carrying
the spatial ``site``/``n_sites`` axis, which downstream backends
(``kernels/lut_serve.py``, ``core/rtl.py``, ``serve/artifact.py``) use to
recover the shared-table structure from the flat SSA program.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.dais import DaisProgram, Reg, Segment, _tree_add
from repro.core.hgq_layers import HGQConv1D, HGQDense
from repro.core.lut_layers import LUTConv1D, LUTConv2D, LUTDense, _same_pads
from repro.core.quant import int_bits, quantize_to_int
from repro.core.tables import extract_tables
from repro.nn.base import Aux


# --------------------------------------------------------------------------- #
# graph spec
# --------------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class GraphInput:
    """Input tensor spec: per-example shape (channels-last) and its grid."""

    shape: Tuple[int, ...]       # e.g. (T, C), (H, W, C), or (C,)
    f: int                       # fractional bits of the pre-quantized input
    i: int                       # integer bits
    signed: bool = True


@dataclasses.dataclass(frozen=True)
class Flatten:
    """Collapse all spatial axes into the channel axis (site-major order)."""


@dataclasses.dataclass(frozen=True)
class ReLU:
    """Standalone relu on integer codes: clamp-at-zero saturating requant."""


@dataclasses.dataclass(frozen=True)
class WindowSum:
    """Per-channel sum over every spatial site (window-count accumulation,
    set pooling).

    Also a trainable node with no parameters: ``apply`` sums a
    ``(B, *sites, C)`` activation over its site axes, so one node list both
    trains (``make_lut_train_step``) and lowers.
    """

    def init(self, key) -> dict:
        return {}

    def apply(self, params: dict, x, *, train: bool = False):
        if x.ndim < 3:
            raise ValueError(f"WindowSum needs (B, sites..., C), got {x.shape}")
        return jnp.sum(x, axis=tuple(range(1, x.ndim - 1))), Aux.zero()


@dataclasses.dataclass
class ModelGraph:
    """A chain of layer nodes / structural ops over a quantized input."""

    input: GraphInput
    nodes: List[object]


# --------------------------------------------------------------------------- #
# lowering registry
# --------------------------------------------------------------------------- #
_LOWERINGS: Dict[type, Callable] = {}


def register_lowering(*node_types: type):
    """Register the DAIS lowering for one or more graph-node types.

    The decorated function has signature ``fn(ctx, node, params, regs) ->
    regs``: ``regs`` is the ndarray of SSA register ids shaped like the
    activation tensor; the function emits instructions on ``ctx.prog`` plus
    one :class:`Segment` per spatial site, and returns the new register
    grid.
    """
    def deco(fn):
        for t in node_types:
            _LOWERINGS[t] = fn
        return fn
    return deco


@dataclasses.dataclass
class _Ctx:
    prog: DaisProgram
    lid: int = 0
    _pads: Dict[int, int] = dataclasses.field(default_factory=dict)

    def pad_reg(self, f: int) -> int:
        """CONST 0 register on grid ``f`` (cached): the im2col zero pad."""
        if f not in self._pads:
            self._pads[f] = self.prog.emit("CONST", (0,), Reg(f, 1, True))
        return self._pads[f]


# --------------------------------------------------------------------------- #
# driver
# --------------------------------------------------------------------------- #
def lower(graph: ModelGraph, params_list: Sequence, *,
          optimize: bool = False) -> DaisProgram:
    """Lower a :class:`ModelGraph` to a DAIS program.

    ``params_list`` aligns with ``graph.nodes`` (``None`` for structural
    ops).  The float input is assumed pre-quantized to the input grid; each
    layer's quantizers govern all internal grids from there on.

    ``optimize=True`` runs the dead-cell elimination pass
    (:func:`repro.core.opt.eliminate_dead_cells`) on the lowered program:
    cells that β·EBOPs pruning drove to a constant-0 truth table — which
    the per-cell emission below cannot see, it marks and skips only
    *width*-pruned cells (``m <= 0 or n <= 0``) — are folded out, dead
    chains are compacted, and shared-table rows with no live lookup are
    sliced from both the tables and every site's gather.  The optimized
    program is bit-exact (``tests/test_opt.py`` property-tests it; serving
    re-gates it with ``verify_engine`` against the unoptimized oracle).
    """
    if len(params_list) != len(graph.nodes):
        raise ValueError(
            f"params_list has {len(params_list)} entries for "
            f"{len(graph.nodes)} graph nodes")
    gi = graph.input
    prog = DaisProgram()
    n_in = int(np.prod(gi.shape))
    prog.input_f = [gi.f] * n_in
    prog.input_signed = [gi.signed] * n_in
    w = gi.f + gi.i + (1 if gi.signed else 0)
    regs = np.asarray(
        [prog.emit("IN", (k,), Reg(gi.f, w, gi.signed)) for k in range(n_in)],
        np.int64).reshape(gi.shape)

    ctx = _Ctx(prog)
    for lid, (node, params) in enumerate(zip(graph.nodes, params_list)):
        fn = _LOWERINGS.get(type(node))
        if fn is None:
            raise TypeError(f"no lowering registered for {type(node)}; "
                            f"add one with @register_lowering")
        ctx.lid = lid
        regs = fn(ctx, node, params, regs)

    outputs = [int(r) for r in np.asarray(regs).reshape(-1)]
    prog.outputs = outputs
    prog.output_f = [prog.instrs[r].reg.f for r in outputs]
    # the IR boundary gate: a lowering that emitted a structurally broken
    # program fails here with located diagnostics, not deep inside an
    # engine (core/analysis.py; DCE below re-verifies its own output)
    from repro.core.analysis import verify_program
    verify_program(prog)
    if optimize:
        from repro.core.opt import eliminate_dead_cells
        prog, _report = eliminate_dead_cells(prog)
    return prog


def compile_sequential(layers: Sequence, params_list: Sequence[dict],
                       input_f: int, input_i: int,
                       input_signed: bool = True, *,
                       optimize: bool = False) -> DaisProgram:
    """Lower a flat stack of dense layers: the trivial chain ModelGraph."""
    graph = ModelGraph(
        input=GraphInput(shape=(layers[0].c_in,), f=input_f, i=input_i,
                         signed=input_signed),
        nodes=list(layers))
    return lower(graph, list(params_list), optimize=optimize)


# --------------------------------------------------------------------------- #
# patch extraction over register grids (the im2col of the integer domain)
# --------------------------------------------------------------------------- #
def _pad_rows(ctx: _Ctx, regs: np.ndarray) -> np.ndarray:
    """One row of zero-pad registers matching each channel's grid."""
    return np.asarray(
        [ctx.pad_reg(ctx.prog.instrs[int(r)].reg.f) for r in regs],
        np.int64)


def _patches_1d(ctx: _Ctx, regs: np.ndarray, kernel: int, stride: int,
                padding: str) -> np.ndarray:
    """(T, C) register grid -> (S, kernel*C) patch rows (k-major, c-minor).

    Matches ``lut_layers.im2col_1d`` exactly: SAME pads split
    low-side-first, VALID drops the ragged tail.  Padded positions read a
    cached CONST 0 register on the source channel's grid.
    """
    t = regs.shape[0]
    if padding == "SAME":
        lo, hi = _same_pads(t, kernel, stride)
        pad = _pad_rows(ctx, regs[0])
        regs = np.concatenate([np.tile(pad, (lo, 1)), regs,
                               np.tile(pad, (hi, 1))], axis=0)
    n_out = (regs.shape[0] - kernel) // stride + 1
    idx = np.arange(n_out)[:, None] * stride + np.arange(kernel)[None, :]
    return regs[idx].reshape(n_out, kernel * regs.shape[1])


def _patches_2d(ctx: _Ctx, regs: np.ndarray, kernel: Tuple[int, int],
                stride: Tuple[int, int], padding: str) -> np.ndarray:
    """(H, W, C) register grid -> (OH, OW, kh*kw*C) patch rows."""
    kh, kw = kernel
    sh, sw = stride
    if padding == "SAME":
        hlo, hhi = _same_pads(regs.shape[0], kh, sh)
        wlo, whi = _same_pads(regs.shape[1], kw, sw)
        pad = _pad_rows(ctx, regs[0, 0])
        h, w, c = regs.shape
        padded = np.tile(pad, (h + hlo + hhi, w + wlo + whi, 1))
        padded[hlo:hlo + h, wlo:wlo + w] = regs
        regs = padded
    oh = (regs.shape[0] - kh) // sh + 1
    ow = (regs.shape[1] - kw) // sw + 1
    ih = np.arange(oh)[:, None] * sh + np.arange(kh)[None, :]
    iw = np.arange(ow)[:, None] * sw + np.arange(kw)[None, :]
    p = regs[ih[:, None, :, None], iw[None, :, None, :], :]
    return p.reshape(oh, ow, kh * kw * regs.shape[2])


# --------------------------------------------------------------------------- #
# LUT layers: tables extracted once, instantiated per site
# --------------------------------------------------------------------------- #
def _emit_lut_site(prog: DaisProgram, lid: int, t, in_regs: List[int]) -> List[int]:
    """One site of a LUT layer against the *shared* tables ``t``."""
    F = t.common_f_out()
    out_regs: List[int] = []
    for i in range(t.c_out):
        terms: List[int] = []
        for j in range(t.c_in):
            m = int(t.in_width[j, i])
            n = int(t.out_width[j, i])
            if m <= 0 or n <= 0:
                continue  # pruned cell
            src = in_regs[j]
            rq = prog.emit(
                "REQUANT",
                (src, int(t.f_in[j, i]), int(t.i_in[j, i]), True, "WRAP",
                 prog.instrs[src].reg.f),
                Reg(int(t.f_in[j, i]), m, True))
            lu = prog.emit("LLUT", (rq, lid, j, i),
                           Reg(int(t.f_out[j, i]), n, True))
            if int(t.f_out[j, i]) != F:
                lu = prog.emit("CMUL", (lu, 1 << (F - int(t.f_out[j, i])), 0),
                               Reg(F, n + F - int(t.f_out[j, i]), True))
            terms.append(lu)
        if not terms:  # fully pruned output
            out_regs.append(prog.emit("CONST", (0,), Reg(F, 1, True)))
        else:
            out_regs.append(_tree_add(prog, terms, F))
    return out_regs


def _emit_lut_sites(ctx: _Ctx, t, sites: np.ndarray) -> np.ndarray:
    """All sites of one LUT layer; every site shares ``tables[ctx.lid]``."""
    n_sites = sites.shape[0]
    outs = np.empty((n_sites, t.c_out), np.int64)
    for s in range(n_sites):
        in_regs = [int(r) for r in sites[s]]
        out_regs = _emit_lut_site(ctx.prog, ctx.lid, t, in_regs)
        ctx.prog.segments.append(Segment(
            kind="lut", layer_id=ctx.lid, in_regs=tuple(in_regs),
            out_regs=tuple(out_regs), site=s, n_sites=n_sites))
        outs[s] = out_regs
    return outs


@register_lowering(LUTDense)
def _lower_lut_dense(ctx: _Ctx, layer: LUTDense, params, regs) -> np.ndarray:
    # time-distributed over any leading spatial axes (e.g. the per-window
    # head of the PID model): one shared table set, one segment per site
    sites = regs.reshape(-1, regs.shape[-1])
    if sites.shape[1] != layer.c_in:
        raise ValueError(f"LUTDense expects {layer.c_in} channels, "
                         f"got state shape {regs.shape}")
    t = extract_tables(layer, params)
    ctx.prog.tables[ctx.lid] = t
    outs = _emit_lut_sites(ctx, t, sites)
    return outs.reshape(regs.shape[:-1] + (layer.c_out,))


@register_lowering(LUTConv1D)
def _lower_lut_conv1d(ctx: _Ctx, layer: LUTConv1D, params, regs) -> np.ndarray:
    if regs.ndim != 2:
        raise ValueError(f"LUTConv1D expects (T, C) state, got {regs.shape}")
    patches = _patches_1d(ctx, regs, layer.kernel, layer.stride, layer.padding)
    t = extract_tables(layer, params)       # conv shares its dense cell grid
    ctx.prog.tables[ctx.lid] = t
    return _emit_lut_sites(ctx, t, patches)


@register_lowering(LUTConv2D)
def _lower_lut_conv2d(ctx: _Ctx, layer: LUTConv2D, params, regs) -> np.ndarray:
    if regs.ndim != 3:
        raise ValueError(f"LUTConv2D expects (H, W, C) state, got {regs.shape}")
    patches = _patches_2d(ctx, regs, layer.kernel, layer.stride, layer.padding)
    oh, ow = patches.shape[:2]
    t = extract_tables(layer, params)
    ctx.prog.tables[ctx.lid] = t
    outs = _emit_lut_sites(ctx, t, patches.reshape(oh * ow, -1))
    return outs.reshape(oh, ow, layer.c_out)


# --------------------------------------------------------------------------- #
# HGQ layers: weight codes quantized once, constant-multiply trees per site
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class _HgqSpec:
    """Per-layer constants shared by every spatial site."""

    fa: np.ndarray               # (c_in,) activation fractional bits
    ia: np.ndarray               # (c_in,)
    fw: np.ndarray               # (c_in, c_out)
    w_codes: np.ndarray          # (c_in, c_out) integer weight codes
    bias: np.ndarray             # (c_out,) float biases (rounded onto F)


def _hgq_spec(layer: HGQDense, params: dict) -> _HgqSpec:
    fa, ia = int_bits(params["q_a"], layer.q_a)
    fw, iw = int_bits(params["q_w"], layer.q_w)
    fa = np.broadcast_to(fa, (layer.c_in,))
    ia = np.broadcast_to(ia, (layer.c_in,))
    w = np.asarray(params["w"], np.float64)
    w_codes = quantize_to_int(w, fw, iw, layer.q_w.signed, layer.q_w.overflow)
    bias = np.asarray(params.get("b", np.zeros(layer.c_out)), np.float64)
    return _HgqSpec(fa=fa, ia=ia, fw=fw, w_codes=w_codes, bias=bias)


def _emit_hgq_site(prog: DaisProgram, layer: HGQDense, spec: _HgqSpec,
                   in_regs: List[int]) -> List[int]:
    """One site of an HGQ layer: per-element constant multiplies + adds.

    Activation quantizer grids come from q_a; weights use their per-element
    (f, i).  Nonlinear activations other than relu are not representable in
    plain DAIS (da4ml would emit them as L-LUTs); relu is lowered as a
    saturating REQUANT with lo clamped at 0 via the unsigned grid.
    """
    fa, ia, fw, w_codes, bias = (spec.fa, spec.ia, spec.fw, spec.w_codes,
                                 spec.bias)
    ka = 1 if layer.q_a.signed else 0
    # quantize inputs once per j
    act_regs = []
    for j in range(layer.c_in):
        src = in_regs[j]
        wdt = int(fa[j] + ia[j] + ka)
        act_regs.append(prog.emit(
            "REQUANT",
            (src, int(fa[j]), int(ia[j]), layer.q_a.signed,
             layer.q_a.overflow, prog.instrs[src].reg.f),
            Reg(int(fa[j]), max(wdt, 1), layer.q_a.signed)))

    out_regs: List[int] = []
    for i in range(layer.c_out):
        F = int(max((fw[j, i] + fa[j]) for j in range(layer.c_in)))
        terms: List[int] = []
        for j in range(layer.c_in):
            code = int(w_codes[j, i])
            if code == 0:
                continue
            f_prod = int(fw[j, i] + fa[j])
            wdt = prog.instrs[act_regs[j]].reg.width + \
                max(abs(code).bit_length() + 1, 1)
            r = prog.emit("CMUL", (act_regs[j], code, int(fw[j, i])),
                          Reg(f_prod, wdt, True))
            if f_prod != F:
                r = prog.emit("CMUL", (r, 1 << (F - f_prod), 0),
                              Reg(F, wdt + F - f_prod, True))
            terms.append(r)
        b_code = int(np.round(bias[i] * 2.0 ** F))
        b_width = max(abs(b_code).bit_length() + 1, 1)
        if b_code != 0 or not terms:
            terms.append(prog.emit("CONST", (b_code,), Reg(F, b_width, True)))
        acc = _tree_add(prog, terms, F)
        if layer.activation == "relu":
            # relu == clamp to the non-negative grid of the same precision
            wdt = prog.instrs[acc].reg.width
            acc = prog.emit("REQUANT", (acc, F, max(wdt - F, 1), False, "SAT", F),
                            Reg(F, wdt, False))
        elif layer.activation is not None:
            raise NotImplementedError(
                f"activation {layer.activation!r} needs an L-LUT lowering")
        out_regs.append(acc)
    return out_regs


def _emit_hgq_sites(ctx: _Ctx, layer: HGQDense, spec: _HgqSpec,
                    sites: np.ndarray) -> np.ndarray:
    n_sites = sites.shape[0]
    outs = np.empty((n_sites, layer.c_out), np.int64)
    for s in range(n_sites):
        in_regs = [int(r) for r in sites[s]]
        out_regs = _emit_hgq_site(ctx.prog, layer, spec, in_regs)
        ctx.prog.segments.append(Segment(
            kind="hgq", layer_id=ctx.lid, in_regs=tuple(in_regs),
            out_regs=tuple(out_regs), site=s, n_sites=n_sites))
        outs[s] = out_regs
    return outs


@register_lowering(HGQDense)
def _lower_hgq_dense(ctx: _Ctx, layer: HGQDense, params, regs) -> np.ndarray:
    sites = regs.reshape(-1, regs.shape[-1])
    if sites.shape[1] != layer.c_in:
        raise ValueError(f"HGQDense expects {layer.c_in} channels, "
                         f"got state shape {regs.shape}")
    outs = _emit_hgq_sites(ctx, layer, _hgq_spec(layer, params), sites)
    return outs.reshape(regs.shape[:-1] + (layer.c_out,))


@register_lowering(HGQConv1D)
def _lower_hgq_conv1d(ctx: _Ctx, layer: HGQConv1D, params, regs) -> np.ndarray:
    if regs.ndim != 2:
        raise ValueError(f"HGQConv1D expects (T, C) state, got {regs.shape}")
    patches = _patches_1d(ctx, regs, layer.kernel, layer.stride, layer.padding)
    dense = layer.dense
    return _emit_hgq_sites(ctx, dense, _hgq_spec(dense, params), patches)


# --------------------------------------------------------------------------- #
# structural ops
# --------------------------------------------------------------------------- #
@register_lowering(Flatten)
def _lower_flatten(ctx: _Ctx, node, params, regs) -> np.ndarray:
    # pure index manipulation: site-major flatten, no instructions emitted
    return regs.reshape(-1)


@register_lowering(ReLU)
def _lower_relu(ctx: _Ctx, node, params, regs) -> np.ndarray:
    flat = regs.reshape(-1)
    outs = np.empty(flat.shape, np.int64)
    for s, r in enumerate(flat):
        r = int(r)
        reg = ctx.prog.instrs[r].reg
        f = reg.f
        out = ctx.prog.emit(
            "REQUANT", (r, f, max(reg.width - f, 1), False, "SAT", f),
            Reg(f, reg.width, False))
        ctx.prog.segments.append(Segment(
            kind="relu", layer_id=ctx.lid, in_regs=(r,), out_regs=(out,),
            site=s, n_sites=flat.size))
        outs[s] = out
    return outs.reshape(regs.shape)


@register_lowering(WindowSum)
def _lower_window_sum(ctx: _Ctx, node, params, regs) -> np.ndarray:
    if regs.ndim < 2:
        raise ValueError(f"WindowSum needs a spatial axis, got {regs.shape}")
    sites = regs.reshape(-1, regs.shape[-1])        # (S, C)
    c = sites.shape[1]
    outs = np.empty((c,), np.int64)
    for ch in range(c):
        in_regs = [int(r) for r in sites[:, ch]]
        f = max(ctx.prog.instrs[r].reg.f for r in in_regs)
        acc = _tree_add(ctx.prog, list(in_regs), f)
        ctx.prog.segments.append(Segment(
            kind="acc", layer_id=ctx.lid, in_regs=tuple(in_regs),
            out_regs=(acc,), site=ch, n_sites=c))
        outs[ch] = acc
    return outs
