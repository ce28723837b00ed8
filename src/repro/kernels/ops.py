"""Public jit'd wrappers around the Pallas kernels.

* compiles the kernels through Mosaic on a TPU and auto-selects
  ``interpret=True`` on any other backend (the kernel body then runs as
  pure-Python/jnp and is validated against ref.py),
* pairs the fused LUT-Dense forward (``lut_dense.py``) with the fused
  recompute backward (``lut_dense_bwd.py``) through a ``custom_vjp`` — both
  train and eval run kernel-side, with no (B, C_in, H, C_out) HBM
  intermediate in either direction.

Train vs eval paths
-------------------
``lut_dense``        takes already-rounded (integer-valued, float-dtype)
                     bit-width arrays — the serving/eval entry point.  Its
                     VJP is the Pallas backward, which also produces the
                     analytic surrogate gradients for (f_in, f_out, i_out)
                     and an exact zero for i_in (WRAP).
``lut_dense_train``  takes the *continuous* bit-width parameters, applies
                     the same clip + ``round_ste`` chain as
                     ``core.quant.fake_quant`` and calls ``lut_dense`` — so
                     ``jax.grad`` through it reaches the quantizer
                     parameters exactly as on the einsum path.

The einsum train-mode reference (``ref.lut_dense_train_ref``) stays the test
oracle for both directions: ``jax.grad`` of it yields the surrogate
gradients the fused backward must reproduce.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.fake_quant import fake_quant_fused
from repro.kernels.lut_dense import lut_dense_fused
from repro.kernels.lut_dense_bwd import lut_dense_bwd_fused


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------------- #
# lut_dense: fused forward + fused recompute backward
# --------------------------------------------------------------------------- #
@jax.custom_vjp
def lut_dense(x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out):
    return lut_dense_fused(x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out,
                           interpret=not _on_tpu())


def _ld_fwd(x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out):
    y = lut_dense_fused(x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out,
                        interpret=not _on_tpu())
    return y, (x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out)


def _ld_bwd(res, g):
    x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out = res
    dx, dw0, db0, dwo, dbo, dfi, dfo, dio = lut_dense_bwd_fused(
        x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out, g,
        interpret=not _on_tpu())
    # i_in has no surrogate under WRAP (core.quant._fq_bwd returns 0 there).
    return (dx.astype(x.dtype), dw0.astype(w0.dtype), db0.astype(b0.dtype),
            dwo.astype(w_out.dtype), dbo.astype(b_out.dtype),
            dfi.astype(f_in.dtype), jnp.zeros_like(i_in),
            dfo.astype(f_out.dtype), dio.astype(i_out.dtype))


lut_dense.defvjp(_ld_fwd, _ld_bwd)


def lut_dense_train(x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out,
                    *, clip_in=None, clip_out=None):
    """Train-mode fused LUT-Dense: continuous (un-rounded) bit-width arrays.

    Array-level convenience for callers that hold raw width arrays rather
    than a quantizer param dict (``LUTDense._fused_forward`` goes through
    ``core.quant.ste_bits`` + :func:`lut_dense` directly).
    ``clip_in``/``clip_out`` are optional ``((min_f, max_f), (min_i, max_i))``
    bounds; the clip + STE-round chain is ``core.quant.ste_bits`` itself, so
    gradients reach the bit-width parameters with ``fake_quant``'s exact
    semantics (including 0-bit pruning — a cell whose rounded width is ≤ 0
    contributes zero forward and zero weight gradient).
    """
    from repro.core.quant import QuantConfig, ste_bits

    inf = float("inf")

    def bits(f, i, clip):
        (mf, xf), (mi, xi) = clip if clip is not None else \
            ((-inf, inf), (-inf, inf))
        cfg = QuantConfig(min_f=mf, max_f=xf, min_i=mi, max_i=xi)
        return ste_bits({"f": f, "i": i}, cfg)

    f_in, i_in = bits(f_in, i_in, clip_in)
    f_out, i_out = bits(f_out, i_out, clip_out)
    return lut_dense(x, w0, b0, w_out, b_out, f_in, i_in, f_out, i_out)


# --------------------------------------------------------------------------- #
# fake_quant
# --------------------------------------------------------------------------- #
@functools.partial(jax.jit, static_argnames=("signed", "overflow"))
def fake_quant(x, f, i, *, signed: bool = True, overflow: str = "SAT"):
    return fake_quant_fused(x, f, i, signed=signed, overflow=overflow,
                            interpret=not _on_tpu())


# --------------------------------------------------------------------------- #
# integer serving engine (post-training artifact path)
# --------------------------------------------------------------------------- #
# The train/eval kernels above run the *float* fake-quant model; after
# `extract_tables` + `compile_sequential` the deployable artifact is an
# integer DAIS program, and `lut_serve` lowers it onto the accelerator as
# batched table gathers + exact integer arithmetic.  Re-exported here so the
# serving stack (`launch/serve.py --engine tables`, benchmarks, tests) has
# one import surface for every kernel-backed entry point.
from repro.kernels.lut_serve import (ServeEngine, compile_program,  # noqa: E402
                                     lower_tables, verify_engine)

# re-exports of the oracles for test convenience
lut_dense_ref = _ref.lut_dense_ref
lut_dense_train_ref = _ref.lut_dense_train_ref
fake_quant_ref = _ref.fake_quant_ref
