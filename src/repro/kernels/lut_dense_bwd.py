"""Fused LUT-Dense *training* backward as a Pallas TPU kernel.

The einsum VJP of Algorithm 1 re-materialises the (B, C_in, H, C_out) hidden
tensor in HBM a second time (once saved by the forward, once rebuilt by the
cotangent chain).  This kernel instead recomputes the per-tile hidden
activations flash-attention-style: the grid runs over
(C_out-tiles × batch-tiles), each instance re-evaluates the broadcast →
WRAP-quant → tanh-MLP chain for its (TB, TCO) tile one C_in slice at a time,
so the only per-``j`` intermediate — (TB, H, TCO) — lives in VMEM and nothing
of size B·C_in·H·C_out ever touches HBM.

Gradients produced (matching ``jax.grad`` of
:func:`repro.kernels.ref.lut_dense_train_ref`, i.e. the analytic surrogate
VJPs of ``core/quant.py``):

* ``dx``           — identity-STE through the WRAP input quantizer,
* ``dw0/db0/dw_out/db_out`` — the tiny-MLP VJP,
* ``df_in``        — WRAP rounding-error surrogate ``ln2·(x - round(x))``,
* ``df_out/di_out``— SAT rounding-error + saturation-boundary surrogates.

``di_in`` is identically zero under WRAP (a wrap is invisible to the loss
surface) and is emitted by the caller, not the kernel.

Reductions: batch is the *innermost* grid axis, so the weight / bit-width
gradient blocks (whose index maps ignore it) are revisited consecutively and
accumulated in VMEM — the standard Pallas output-accumulation pattern.  ``dx``
instead gets one partial per C_out-tile (shape (n_co_tiles, B, C_in)) summed
by the host wrapper; n_co_tiles is tiny so the extra HBM is negligible.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.kernels.lut_dense import DEF_TB, DEF_TCO, _column

LOG2 = float(np.log(2.0))


def _lut_dense_bwd_kernel(x_ref, w0_ref, b0_ref, wo_ref, bo_ref,
                          fi_ref, ii_ref, fo_ref, io_ref, g_ref,
                          dx_ref, dw0_ref, db0_ref, dwo_ref, dbo_ref,
                          dfi_ref, dfo_ref, dio_ref):
    """One (TB, TCO) cotangent tile; fori over C_in, recompute per slice."""
    ib = pl.program_id(1)

    @pl.when(ib == 0)
    def _init():
        for r in (dw0_ref, db0_ref, dwo_ref, dbo_ref, dfi_ref, dfo_ref,
                  dio_ref):
            r[...] = jnp.zeros(r.shape, r.dtype)

    x = x_ref[...].astype(jnp.float32)                       # (TB, C_in)
    g = g_ref[...].astype(jnp.float32)                       # (TB, TCO)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def body(j, acc_dx):
        row = lambda ref: ref[pl.ds(j, 1), :]                # (1, TCO)
        slab = lambda ref: ref[pl.ds(j, 1)][0]               # (H, TCO)
        xj = _column(x, col, j)                              # (TB, 1)
        fi, ii = row(fi_ref), row(ii_ref)
        fo, io = row(fo_ref), row(io_ref)
        bo = row(bo_ref)
        w0, b0, wo = slab(w0_ref), slab(b0_ref), slab(wo_ref)

        # ---- forward recompute (expressions identical to lut_dense.py) ----
        scale_i = jnp.exp2(-fi)
        r_in = jnp.round(xj / scale_i) * scale_i             # (TB, TCO)
        lo_i = -jnp.exp2(ii)
        alive_i = fi + ii + 1.0 > 0.0
        xq = lo_i + jnp.mod(r_in - lo_i, jnp.exp2(ii) * 2.0)
        xq = jnp.where(alive_i, xq, 0.0)
        h = jnp.tanh(xq[:, None, :] * w0[None] + b0[None])   # (TB, H, TCO)
        y = jnp.sum(h * wo[None], axis=1) + bo               # (TB, TCO)
        scale_o = jnp.exp2(-fo)
        r_out = jnp.round(y / scale_o) * scale_o
        chi = r_out > jnp.exp2(io) - scale_o
        clo = r_out < -jnp.exp2(io)
        alive_o = fo + io + 1.0 > 0.0

        # ---- SAT output-quantizer surrogate VJP (core.quant._fq_bwd) ----
        gy = jnp.where(alive_o & ~(chi | clo), g, 0.0)
        dfo_s = jnp.where(chi, LOG2 * scale_o, LOG2 * (y - r_out))
        dfo_s = jnp.where(clo, 0.0, dfo_s)
        dio_s = jnp.where(chi, LOG2 * jnp.exp2(io),
                          jnp.where(clo, -LOG2 * jnp.exp2(io), 0.0))
        dfo_j = jnp.sum(jnp.where(alive_o, dfo_s * g, 0.0), 0, keepdims=True)
        dio_j = jnp.sum(jnp.where(alive_o, dio_s * g, 0.0), 0, keepdims=True)

        # ---- tiny-MLP VJP ----
        dbo_j = jnp.sum(gy, axis=0, keepdims=True)           # (1, TCO)
        dwo_j = jnp.sum(h * gy[:, None, :], axis=0)          # (H, TCO)
        gz = gy[:, None, :] * wo[None] * (1.0 - h * h)       # (TB, H, TCO)
        db0_j = jnp.sum(gz, axis=0)
        dw0_j = jnp.sum(gz * xq[:, None, :], axis=0)
        gxq = jnp.sum(gz * w0[None], axis=1)                 # (TB, TCO)

        # ---- WRAP input-quantizer surrogate VJP ----
        dfi_j = jnp.sum(jnp.where(alive_i, LOG2 * (xj - r_in) * gxq, 0.0),
                        0, keepdims=True)
        gx_j = jnp.sum(jnp.where(alive_i, gxq, 0.0), 1, keepdims=True)

        for ref, val in ((dw0_ref, dw0_j), (db0_ref, db0_j),
                         (dwo_ref, dwo_j)):
            ref[pl.ds(j, 1)] += val[None]
        for ref, val in ((dbo_ref, dbo_j), (dfi_ref, dfi_j),
                         (dfo_ref, dfo_j), (dio_ref, dio_j)):
            ref[pl.ds(j, 1), :] += val
        return jnp.where(col == j, gx_j, acc_dx)             # column j of dx

    acc_dx = jax.lax.fori_loop(0, x.shape[1], body,
                               jnp.zeros(x.shape, jnp.float32))
    dx_ref[...] = acc_dx[None]


@functools.partial(jax.jit, static_argnames=("tb", "tco", "interpret"))
def lut_dense_bwd_fused(x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out, g,
                        *, tb: int = DEF_TB, tco: int = DEF_TCO,
                        interpret: bool = False):
    """Train-mode LUT-Dense backward.

    Same input shapes as :func:`repro.kernels.lut_dense.lut_dense_fused`
    plus the output cotangent ``g`` (B, C_out); bit-width arrays must already
    be STE-rounded (``core.quant.ste_bits`` does this upstream).
    Returns ``(dx, dw0, db0, dw_out, db_out, df_in, df_out, di_out)`` —
    ``di_in`` is identically zero under WRAP and left to the caller.
    """
    b, c_in = x.shape
    h = w0.shape[1]
    c_out = w0.shape[-1]
    tb = min(tb, max(b, 1))
    tco = min(tco, max(c_out, 1))

    pb, pco = -b % tb, -c_out % tco
    if pb:
        x = jnp.pad(x, ((0, pb), (0, 0)))
    if pco:
        w0, b0, w_out = (jnp.pad(a, ((0, 0), (0, 0), (0, pco)))
                         for a in (w0, b0, w_out))
        b_out, f_in, i_in, f_out, i_out = (
            jnp.pad(a, ((0, 0), (0, pco)))
            for a in (b_out, f_in, i_in, f_out, i_out))
    # zero-padded cotangent rows/cols contribute exactly zero to every grad
    g = jnp.pad(g, ((0, pb), (0, pco)))
    bp, cop = b + pb, c_out + pco
    n_ic, n_ib = cop // tco, bp // tb

    grid = (n_ic, n_ib)  # batch innermost -> weight grads accumulate in VMEM
    spec_x = pl.BlockSpec((tb, c_in), lambda ic, ib: (ib, 0))
    spec_w = pl.BlockSpec((c_in, h, tco), lambda ic, ib: (0, 0, ic))
    spec_q = pl.BlockSpec((c_in, tco), lambda ic, ib: (0, ic))
    spec_g = pl.BlockSpec((tb, tco), lambda ic, ib: (ib, ic))
    spec_dx = pl.BlockSpec((1, tb, c_in), lambda ic, ib: (ic, ib, 0))

    f32 = jnp.float32
    outs = pl.pallas_call(
        _lut_dense_bwd_kernel,
        grid=grid,
        in_specs=[spec_x, spec_w, spec_w, spec_w, spec_q,
                  spec_q, spec_q, spec_q, spec_q, spec_g],
        out_specs=[spec_dx, spec_w, spec_w, spec_w,
                   spec_q, spec_q, spec_q, spec_q],
        out_shape=[
            jax.ShapeDtypeStruct((n_ic, bp, c_in), f32),      # dx partials
            jax.ShapeDtypeStruct((c_in, h, cop), f32),        # dw0
            jax.ShapeDtypeStruct((c_in, h, cop), f32),        # db0
            jax.ShapeDtypeStruct((c_in, h, cop), f32),        # dw_out
            jax.ShapeDtypeStruct((c_in, cop), f32),           # db_out
            jax.ShapeDtypeStruct((c_in, cop), f32),           # df_in
            jax.ShapeDtypeStruct((c_in, cop), f32),           # df_out
            jax.ShapeDtypeStruct((c_in, cop), f32),           # di_out
        ],
        interpret=interpret,
    )(x.astype(f32), w0, b0, w_out, b_out,
      f_in.astype(f32), i_in.astype(f32),
      f_out.astype(f32), i_out.astype(f32), g.astype(f32))

    dxp, dw0, db0, dwo, dbo, dfi, dfo, dio = outs
    dx = jnp.sum(dxp, axis=0)[:b]
    return (dx, dw0[..., :c_out], db0[..., :c_out], dwo[..., :c_out],
            dbo[..., :c_out], dfi[..., :c_out], dfo[..., :c_out],
            dio[..., :c_out])
