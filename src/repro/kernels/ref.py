"""Pure-jnp oracles for the Pallas kernels.

These are the ground truth every kernel is validated against (shape/dtype
sweeps in tests/test_kernels.py).  They mirror the einsum formulation of
Algorithm 1 — i.e. exactly what the paper's GPU implementation computes — in
*eval* mode: bit-width parameters are already-rounded integers passed as
arrays, so oracle and kernel share one definition of the quantization grid.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array


def fake_quant_ref(x: Array, f: Array, i: Array, signed: bool, overflow: str) -> Array:
    """Fixed-point projection with integer (f, i) bit-width arrays."""
    x = x.astype(jnp.float32)
    f = jnp.broadcast_to(f, x.shape).astype(jnp.float32)
    i = jnp.broadcast_to(i, x.shape).astype(jnp.float32)
    scale = jnp.exp2(-f)
    hi = jnp.exp2(i) - scale
    lo = -jnp.exp2(i) if signed else jnp.zeros_like(hi)
    q = jnp.round(x / scale) * scale
    if overflow == "SAT":
        q = jnp.clip(q, lo, hi)
    else:
        span = hi - lo + scale
        q = lo + jnp.mod(q - lo, span)
    width = f + i + (1.0 if signed else 0.0)
    return jnp.where(width > 0.0, q, 0.0)


def lut_dense_ref(
    x: Array,            # (B, C_in)
    w0: Array,           # (C_in, H, C_out)   first-level MLP weights
    b0: Array,           # (C_in, H, C_out)
    w_out: Array,        # (C_in, H, C_out)   output projection
    b_out: Array,        # (C_in, C_out)
    f_in: Array,         # (C_in, C_out) int widths of the WRAP input quantizer
    i_in: Array,
    f_out: Array,        # (C_in, C_out) int widths of the SAT output quantizer
    i_out: Array,
) -> Array:
    """Eval-mode LUT-Dense forward (Eq. 1 / Algorithm 1), single hidden layer.

    Layout note: weights use (C_in, H, C_out) so the kernel keeps C_out on the
    TPU lane dimension; the training layer stores (C_in, C_out, H) and ops.py
    transposes once at call time.
    """
    xb = jnp.broadcast_to(x[:, :, None], x.shape + (w0.shape[-1],))  # (B, Ci, Co)
    xq = fake_quant_ref(xb, f_in[None], i_in[None], True, "WRAP")
    h = jnp.tanh(xq[:, :, None, :] * w0[None] + b0[None])            # (B, Ci, H, Co)
    y = jnp.sum(h * w_out[None], axis=2) + b_out[None]               # (B, Ci, Co)
    yq = fake_quant_ref(y, f_out[None], i_out[None], True, "SAT")
    return jnp.sum(yq, axis=1)                                       # (B, Co)


def lut_dense_train_ref(
    x: Array, w0: Array, b0: Array, w_out: Array, b_out: Array,
    f_in: Array, i_in: Array, f_out: Array, i_out: Array,
) -> Array:
    """*Differentiable* train-mode oracle for the fused fwd+bwd pair.

    Same math as :func:`lut_dense_ref` but built from ``core.quant``'s
    custom-VJP fake-quantizer, so ``jax.grad`` of this function yields the
    analytic surrogate gradients — for all five weight tensors AND the four
    bit-width arrays — that ``kernels/lut_dense_bwd.py`` must reproduce.
    Bit-width arrays are integer-valued (already STE-rounded), shape
    (C_in, C_out).  This materialises the (B, C_in, H, C_out) hidden tensor
    in HBM; it is the correctness oracle, not a fast path.
    """
    from repro.core.quant import fq_surrogate

    y = lut_cells_train_ref(x, w0, b0, w_out, b_out, f_in, i_in)
    yq = fq_surrogate(y, f_out, i_out, signed=True, overflow="SAT")
    return jnp.sum(yq, axis=1).astype(x.dtype)


def lut_cells_train_ref(x: Array, w0: Array, b0: Array, w_out: Array,
                        b_out: Array, f_in: Array, i_in: Array) -> Array:
    """The first half of :func:`lut_dense_train_ref`: every cell's value
    before its output quantizer, (B, C_in, C_out).  A caller with
    batch-norm normalizes these before quantizing them."""
    from repro.core.quant import fq_surrogate

    xb = jnp.broadcast_to(x[:, :, None].astype(jnp.float32),
                          x.shape + (w0.shape[-1],))
    xq = fq_surrogate(xb, f_in, i_in, signed=True, overflow="WRAP")
    h = jnp.tanh(xq[:, :, None, :] * w0[None] + b0[None])
    return jnp.sum(h * w_out[None], axis=2) + b_out[None]


# --------------------------------------------------------------------------- #
# JSC-PLF (models/plf.py): per-particle encoder, set sum, head
# --------------------------------------------------------------------------- #
# bit-width clip range of the layers' quantizers (QuantConfig defaults)
BIT_MIN, BIT_MAX = -8.0, 12.0
BN_EPS, BN_MOMENTUM = 1e-5, 0.99


def _kernel_layout(p: dict):
    """A LUT-Dense parameter dict's MLP tensors in the (C_in, H, C_out)
    layout of :func:`lut_dense_ref`."""
    return (jnp.transpose(p["w0"], (0, 2, 1)), jnp.transpose(p["b0"], (0, 2, 1)),
            jnp.transpose(p["w_out"], (0, 2, 1)), p["b_out"])


def _bits(q: dict, train: bool):
    """Clipped, rounded (f, i); with a straight-through gradient in training."""
    def r(v):
        c = jnp.clip(v, BIT_MIN, BIT_MAX)
        return c + jax.lax.stop_gradient(jnp.round(c) - c) if train \
            else jnp.round(c)
    return r(q["f"]), r(q["i"])


def _ebops(f_in, i_in, f_out, i_out) -> Array:
    """Eq. 5 of the paper (LUT-6 split into LUT-5s) over a cell grid."""
    m = jnp.maximum(f_in + i_in + 1.0, 0.0)
    n = jnp.maximum(f_out + i_out + 1.0, 0.0)
    cost = jnp.where(m >= 5, jnp.exp2(m - 6) * n, (m / 5) * 2.0 ** -1 * n)
    return jnp.sum(jnp.where((m > 0) & (n > 0), cost, 0.0))


def plf_ref(params, x: Array) -> Array:
    """Eval-mode JSC-PLF forward: (B, P, F) inputs on the input grid ->
    (B, n_classes) logits on the head's output grid.

    ``params`` is ``[encoder, pool, head]`` (the pool's entry is ignored),
    each LUT-Dense dict as ``LUTDense.init`` lays it out.  The encoder runs
    :func:`lut_dense_ref` on every particle with its batch-norm (moving
    statistics) folded into the output projection, the pool is a
    ``jnp.sum`` over particles, the head is :func:`lut_dense_ref` again.
    float32 at the highest matmul precision; no kernels.

    Departure from the paper: the pool is a sum, where the JEDI-linear
    network it follows takes a mean; the two differ by the constant 1/P,
    which the head's input quantizer absorbs.
    """
    enc, _pool, head = params
    b, n_p, n_f = x.shape
    with jax.default_matmul_precision("highest"):
        w0, b0, wo, bo = _kernel_layout(enc)
        inv = enc["bn_scale"] * jax.lax.rsqrt(enc["bn_var"] + BN_EPS)
        wo = wo * inv[:, None, :]
        bo = bo * inv + enc["bn_bias"] - enc["bn_mean"] * inv
        h = lut_dense_ref(x.reshape(b * n_p, n_f).astype(jnp.float32),
                          w0, b0, wo, bo, *_bits(enc["q_in"], False),
                          *_bits(enc["q_out"], False))
        s = jnp.sum(h.reshape(b, n_p, -1), axis=1)
        return lut_dense_ref(s, *_kernel_layout(head), *_bits(head["q_in"], False),
                             *_bits(head["q_out"], False))


def plf_train_ref(params, x: Array, y: Array, beta: float):
    """Train-mode JSC-PLF loss, differentiable in ``params``.

    ``params`` is ``{"l0": encoder, "l1": {}, "l2": head}``, the layout of
    ``make_lut_train_step``.  The encoder's cells (:func:`lut_cells_train_ref`)
    run on every particle; its batch-norm takes its statistics over batch
    and particles; the pool is a ``jnp.sum`` over particles and the head is
    :func:`lut_dense_train_ref`.  The loss is softmax cross-entropy plus
    ``beta`` times the EBOPs of both layers' cells (one table set a layer,
    as the surrogate counts them).  Returns ``(loss, aux)``, ``aux`` holding
    ``ce``, ``ebops`` and the encoder's new moving statistics
    (``bn_mean``, ``bn_var``).  float32 at the highest matmul precision.
    Departure from the paper: the sum pool (see :func:`plf_ref`).
    """
    from repro.core.quant import fq_surrogate

    enc, head = params["l0"], params["l2"]
    b, n_p, n_f = x.shape
    with jax.default_matmul_precision("highest"):
        fi, ii = _bits(enc["q_in"], True)
        fo, io = _bits(enc["q_out"], True)
        cells = lut_cells_train_ref(x.reshape(b * n_p, n_f).astype(jnp.float32),
                                    *_kernel_layout(enc), fi, ii)
        mean, var = jnp.mean(cells, axis=0), jnp.var(cells, axis=0)
        cells = ((cells - mean) * jax.lax.rsqrt(var + BN_EPS) * enc["bn_scale"]
                 + enc["bn_bias"])
        h = jnp.sum(fq_surrogate(cells, fo, io, signed=True, overflow="SAT"),
                    axis=1)
        s = jnp.sum(h.reshape(b, n_p, -1), axis=1)
        hb = (*_bits(head["q_in"], True), *_bits(head["q_out"], True))
        logits = lut_dense_train_ref(s, *_kernel_layout(head), *hb)
        ce = -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(b), y])
        ebops = _ebops(fi, ii, fo, io) + _ebops(*hb)
        stats = {"bn_mean": BN_MOMENTUM * enc["bn_mean"]
                 + (1 - BN_MOMENTUM) * jax.lax.stop_gradient(mean),
                 "bn_var": BN_MOMENTUM * enc["bn_var"]
                 + (1 - BN_MOMENTUM) * jax.lax.stop_gradient(var)}
        return ce + beta * ebops, {"ce": ce, "ebops": ebops, **stats}
