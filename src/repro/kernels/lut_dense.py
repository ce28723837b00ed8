"""Fused LUT-Dense forward as a Pallas TPU kernel.

The einsum formulation of Algorithm 1 materialises the hidden tensor
(B, C_in, H, C_out) in HBM — at the paper's JSC batch size of 16600 that is
~170 MB per layer per step of pure traffic.  On TPU the op is memory-bound
(arithmetic intensity ≈ 2 flops/byte for the naive chain), so the win is to
fuse broadcast → input-WRAP-quant → tanh MLP → output-SAT-quant → Σ_j into a
single VMEM-resident pass: HBM traffic drops to x + weights + output.

Tiling: grid over (batch-tiles, C_out-tiles).  Each program instance holds an
(TB, TCO) accumulator in registers and loops over C_in with a
``jax.lax.fori_loop``; the per-j intermediate is (TB, H, TCO) — H sits on the
sublane axis and C_out on the 128-lane axis, so the tanh/multiply work is
lane-aligned VPU work and nothing of size H·C_in·C_out ever leaves VMEM.

VMEM budget per instance (fp32):
    x-tile      TB·C_in·4
    weights     3·C_in·H·TCO·4  + quant params 4·C_in·TCO·4
    hidden      TB·H·TCO·4
With the default TB=256, TCO=128, H=8, C_in≤64 this is ≈ 5.3 MB « 16 MB VMEM.

This forward serves BOTH the eval and train paths: bit-width arrays arrive
already STE-rounded (``core.quant.ste_bits`` — called by the layer's fused
path and by ``ops.lut_dense_train`` — runs outside the kernel), so the same
kernel is
a fixed-point projection either way, and its ``custom_vjp`` partner —
``lut_dense_bwd.py``, which recomputes the hidden tile instead of saving it —
supplies the training gradients including the quantizer surrogates.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEF_TB = 256    # batch tile (sublane-friendly multiple of 8)
DEF_TCO = 128   # C_out tile (one lane register width)


def _fq_wrap(x, f, i):
    scale = jnp.exp2(-f)
    lo = -jnp.exp2(i)
    span = jnp.exp2(i) * 2.0
    q = jnp.round(x / scale) * scale
    q = lo + jnp.mod(q - lo, span)
    return jnp.where(f + i + 1.0 > 0.0, q, 0.0)


def _fq_sat(x, f, i):
    scale = jnp.exp2(-f)
    hi = jnp.exp2(i) - scale
    lo = -jnp.exp2(i)
    q = jnp.clip(jnp.round(x / scale) * scale, lo, hi)
    return jnp.where(f + i + 1.0 > 0.0, q, 0.0)


def _column(x, col, j):
    """Column ``j`` of ``x`` as (TB, 1), for a traced ``j``.

    Mosaic has no dynamic slice along the lane axis; a masked row sum picks
    the column exactly (every other term is a zero).
    """
    return jnp.sum(jnp.where(col == j, x, 0.0), axis=1, keepdims=True)


def _lut_dense_kernel(x_ref, w0_ref, b0_ref, wo_ref, bo_ref,
                      fi_ref, ii_ref, fo_ref, io_ref, out_ref):
    """One (TB, TCO) output tile; fori over the C_in reduction axis."""
    x = x_ref[...].astype(jnp.float32)                      # (TB, C_in)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)

    def body(j, acc):
        row = lambda ref: ref[pl.ds(j, 1), :]                # (1, TCO)
        slab = lambda ref: ref[pl.ds(j, 1)][0]               # (H, TCO)
        xq = _fq_wrap(_column(x, col, j), row(fi_ref), row(ii_ref))
        h = jnp.tanh(xq[:, None, :] * slab(w0_ref)[None]
                     + slab(b0_ref)[None])                   # (TB, H, TCO)
        y = jnp.sum(h * slab(wo_ref)[None], axis=1) + row(bo_ref)
        return acc + _fq_sat(y, row(fo_ref), row(io_ref))

    acc = jnp.zeros(out_ref.shape, jnp.float32)
    out_ref[...] = jax.lax.fori_loop(0, x.shape[1], body,
                                     acc).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tb", "tco", "interpret"))
def lut_dense_fused(x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out,
                    *, tb: int = DEF_TB, tco: int = DEF_TCO,
                    interpret: bool = False):
    """Fused eval-mode LUT-Dense forward.

    Shapes match :func:`repro.kernels.ref.lut_dense_ref`:
    x (B, C_in); w0/b0/w_out (C_in, H, C_out); b_out & quant params (C_in, C_out).
    """
    b, c_in = x.shape
    c_out = w0.shape[-1]
    tb = min(tb, max(b, 1))
    tco = min(tco, max(c_out, 1))

    pb, pco = -b % tb, -c_out % tco
    if pb:
        x = jnp.pad(x, ((0, pb), (0, 0)))
    if pco:
        w0, b0, w_out = (jnp.pad(a, ((0, 0), (0, 0), (0, pco))) for a in (w0, b0, w_out))
        b_out, f_in, i_in, f_out, i_out = (
            jnp.pad(a, ((0, 0), (0, pco))) for a in (b_out, f_in, i_in, f_out, i_out))
    bp, cop = b + pb, c_out + pco

    grid = (bp // tb, cop // tco)
    bspec_x = pl.BlockSpec((tb, c_in), lambda ib, ic: (ib, 0))
    bspec_w = pl.BlockSpec((c_in, w0.shape[1], tco), lambda ib, ic: (0, 0, ic))
    bspec_q = pl.BlockSpec((c_in, tco), lambda ib, ic: (0, ic))
    bspec_o = pl.BlockSpec((tb, tco), lambda ib, ic: (ib, ic))

    out = pl.pallas_call(
        _lut_dense_kernel,
        grid=grid,
        in_specs=[bspec_x, bspec_w, bspec_w, bspec_w, bspec_q,
                  bspec_q, bspec_q, bspec_q, bspec_q],
        out_specs=bspec_o,
        out_shape=jax.ShapeDtypeStruct((bp, cop), x.dtype),
        interpret=interpret,
    )(x, w0, b0, w_out, b_out,
      f_in.astype(jnp.float32), i_in.astype(jnp.float32),
      f_out.astype(jnp.float32), i_out.astype(jnp.float32))
    return out[:b, :c_out]
