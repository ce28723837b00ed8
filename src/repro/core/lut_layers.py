"""LUT-Dense and LUT-Conv layers (paper §III-A, Algorithm 1).

Each output of a LUT-Dense layer is a *sum of 1-input logical LUTs*:

    a_i = Σ_j  L-LUT_{i,j}( x_j )                                   (Eq. 1)

During training every L-LUT_{i,j} is a tiny MLP (default: one hidden layer of
width ``hidden`` with tanh) evaluated element-wise over the (C_in × C_out)
grid.  Following Algorithm 1 the whole layer is a stack of einsums — one
monolithic GEMM per MLP level — so training runs at dense-layer speed on
MXU/GPU instead of the scatter/gather patterns of prior LAT methods.

Quantizers: WRAP on the (broadcast) inputs — wrapping is free bit-slicing in
hardware — and SAT on the outputs — saturation is resolved offline during
truth-table generation (§III-B).  Both have one trainable (f, i) pair per
(C_in, C_out) cell, so a cell driven to 0 input or output bits is pruned.

``LUTConv1D/2D`` = im2col followed by LUT-Dense (paper §IV-A).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import ebops as ebops_mod
from repro.core.quant import QuantConfig, bitwidth, fake_quant, init_quantizer
from repro.nn.base import Aux

Array = jax.Array

# paper defaults: inputs wrap, outputs saturate.  WRAP gives no gradient to
# the integer-bit parameter (a wrap is invisible to the loss surface), so
# inputs start WIDE (i=4 covers ±16) and the β·EBOPs pressure shrinks them —
# matching HGQ's init-from-range-statistics convention.
Q_IN_DEFAULT = QuantConfig(granularity="element", signed=True, overflow="WRAP",
                           init_f=4.0, init_i=4.0)
Q_OUT_DEFAULT = QuantConfig(granularity="element", signed=True, overflow="SAT",
                            init_f=4.0, init_i=3.0)


@dataclasses.dataclass(frozen=True)
class LUTDense:
    c_in: int
    c_out: int
    hidden: int = 8          # width of the MLP realising each L-LUT
    n_hidden_layers: int = 1  # L_h; paper finds 1 suffices
    activation: str = "tanh"
    use_batchnorm: bool = False
    q_in: QuantConfig = Q_IN_DEFAULT
    q_out: QuantConfig = Q_OUT_DEFAULT
    bn_momentum: float = 0.99
    # Route apply() through the fused Pallas fwd+bwd pair (kernels/): no
    # (B, C_in, H, C_out) HBM intermediate in either direction.  Covers the
    # paper default (1 hidden tanh layer); train-mode batch-norm still needs
    # the batch-wide pre-quant activations for its statistics, so that one
    # combination falls back to the einsum path.
    use_fused: bool = False

    # ----------------------------------------------------------------- init
    def init(self, key: Array) -> dict:
        ks = jax.random.split(key, 2 * (self.n_hidden_layers + 1))
        h, ci, co = self.hidden, self.c_in, self.c_out
        params: dict = {}
        # first level: 1 -> h  (the lone input of each L-LUT)
        params["w0"] = jax.random.normal(ks[0], (ci, co, h), jnp.float32) * 1.0
        params["b0"] = jax.random.normal(ks[1], (ci, co, h), jnp.float32) * 0.5
        for l in range(1, self.n_hidden_layers):
            params[f"w{l}"] = jax.random.normal(ks[2 * l], (ci, co, h, h)) * (h ** -0.5)
            params[f"b{l}"] = jnp.zeros((ci, co, h))
        # last level: h -> 1, scaled so per-cell outputs start O(1/sqrt(C_in))
        params["w_out"] = jax.random.normal(ks[-2], (ci, co, h)) * (h * ci) ** -0.5
        params["b_out"] = jnp.zeros((ci, co))
        params["q_in"] = init_quantizer(self.q_in, (ci, co))
        params["q_out"] = init_quantizer(self.q_out, (ci, co))
        if self.use_batchnorm:
            params["bn_scale"] = jnp.ones((ci, co))
            params["bn_bias"] = jnp.zeros((ci, co))
            params["bn_mean"] = jnp.zeros((ci, co))
            params["bn_var"] = jnp.ones((ci, co))
        return params

    def _act(self, x: Array) -> Array:
        if self.activation == "tanh":
            return jnp.tanh(x)
        if self.activation == "relu":
            return jax.nn.relu(x)
        raise ValueError(self.activation)

    # ----------------------------------------------------------- cell eval
    def cell_mlp(self, params: dict, xq: Array) -> Array:
        """Evaluate all (C_in, C_out) L-LUT MLPs on quantized input ``xq``.

        ``xq``: (..., C_in, C_out) already input-quantized.  Returns the
        pre-output-quantization values, shape (..., C_in, C_out).  This is the
        exact function the truth-table compiler enumerates.
        """
        h = self._act(jnp.einsum("...io,ioh->...ioh", xq, params["w0"]) + params["b0"])
        for l in range(1, self.n_hidden_layers):
            h = self._act(jnp.einsum("...ioh,iohg->...iog", h, params[f"w{l}"])
                          + params[f"b{l}"])
        y = jnp.einsum("...ioh,ioh->...io", h, params["w_out"]) + params["b_out"]
        return y

    def bn_affine(self, params: dict) -> Tuple[Array, Array]:
        """Deployment-time fused BN: y ← y*scale' + bias' from moving stats."""
        inv = params["bn_scale"] * jax.lax.rsqrt(params["bn_var"] + 1e-5)
        return inv, params["bn_bias"] - params["bn_mean"] * inv

    # --------------------------------------------------- fused Pallas path
    def _fused_forward(self, params: dict, x: Array, *, train: bool) -> Array:
        """Forward through the fused Pallas fwd+bwd pair (kernels/ops.py).

        Train mode keeps the continuous bit-width parameters differentiable
        (clip + round-STE via ``core.quant.ste_bits``, surrogate gradients
        from the Pallas backward); eval mode freezes them.  BN is
        folded into the output projection (eval/frozen stats only — the
        caller guarantees not (use_batchnorm and train)).
        """
        if self.n_hidden_layers != 1 or self.activation != "tanh":
            raise NotImplementedError("fused kernel covers the paper default "
                                      "(1 hidden tanh layer)")
        # the kernel pair hardcodes the paper's quantizer scheme, including
        # the zero i_in surrogate that only holds under WRAP
        if (self.q_in.overflow != "WRAP" or self.q_out.overflow != "SAT"
                or not (self.q_in.signed and self.q_out.signed)):
            raise NotImplementedError("fused kernel covers the paper default "
                                      "quantizers (signed WRAP in, signed "
                                      "SAT out)")
        from repro.core.quant import ste_bits
        from repro.kernels import ops as kops

        w0 = jnp.transpose(params["w0"], (0, 2, 1))       # (Ci, H, Co)
        b0 = jnp.transpose(params["b0"], (0, 2, 1))
        wo = jnp.transpose(params["w_out"], (0, 2, 1))
        bo = params["b_out"]
        if self.use_batchnorm:
            scale, bias = self.bn_affine(params)          # (Ci, Co)
            wo = wo * scale[:, None, :]
            bo = bo * scale + bias
        # one source of truth for the clip + round-STE width chain
        fi, ii = ste_bits(params["q_in"], self.q_in, train=train)
        fo, io = ste_bits(params["q_out"], self.q_out, train=train)
        grid = (self.c_in, self.c_out)
        fi, ii, fo, io = (jnp.broadcast_to(a, grid) for a in (fi, ii, fo, io))
        lead = x.shape[:-1]
        xf = x.reshape((-1, self.c_in))
        y = kops.lut_dense(xf, w0, b0, wo, bo, fi, ii, fo, io)
        return y.reshape(lead + (self.c_out,))

    def apply_fused(self, params: dict, x: Array) -> Array:
        """Eval-mode forward through the fused Pallas kernel (serving path)."""
        return self._fused_forward(params, x, train=False)

    # ---------------------------------------------------------------- apply
    def runs_fused(self, *, train: bool) -> bool:
        """Whether :meth:`apply` takes the fused Pallas path.

        BN+train needs batch-wide statistics -> einsum fallback; any other
        structurally unsupported config raises inside ``_fused_forward``.
        """
        return self.use_fused and not (self.use_batchnorm and train)

    def apply(self, params: dict, x: Array, *, train: bool = False) -> Tuple[Array, Aux]:
        if x.shape[-1] != self.c_in:
            raise ValueError(f"expected (..., {self.c_in}), got {x.shape}")
        if self.runs_fused(train=train):
            out = self._fused_forward(params, x, train=train)
            eb = ebops_mod.ebops_lut(bitwidth(params["q_in"], self.q_in),
                                     bitwidth(params["q_out"], self.q_out))
            return out, Aux(ebops=eb, aux_loss=jnp.zeros((), jnp.float32),
                            updates={})
        # Alg.1 line 1-2: broadcast to (..., C_in, C_out) and input-quantize.
        xb = jnp.broadcast_to(x[..., :, None], x.shape + (self.c_out,))
        xq = fake_quant(params["q_in"], xb, self.q_in, train=train)
        y = self.cell_mlp(params, xq)

        updates = {}
        if self.use_batchnorm:
            axes = tuple(range(y.ndim - 2))
            if train:
                mean = jnp.mean(y, axis=axes)
                var = jnp.var(y, axis=axes)
                m = self.bn_momentum
                updates["bn_mean"] = m * params["bn_mean"] + (1 - m) * jax.lax.stop_gradient(mean)
                updates["bn_var"] = m * params["bn_var"] + (1 - m) * jax.lax.stop_gradient(var)
            else:
                mean, var = params["bn_mean"], params["bn_var"]
            y = (y - mean) * jax.lax.rsqrt(var + 1e-5) * params["bn_scale"] + params["bn_bias"]

        yq = fake_quant(params["q_out"], y, self.q_out, train=train)
        out = jnp.sum(yq, axis=-2)  # Σ over C_in — Eq. (1)

        eb = ebops_mod.ebops_lut(bitwidth(params["q_in"], self.q_in),
                                 bitwidth(params["q_out"], self.q_out))
        return out, Aux(ebops=eb, aux_loss=jnp.zeros((), jnp.float32), updates=updates)


# --------------------------------------------------------------------------- #
# im2col helpers + LUT-Conv
# --------------------------------------------------------------------------- #
def _same_pads(size: int, kernel: int, stride: int) -> Tuple[int, int]:
    """SAME padding matching ``jax.lax.conv`` / TF: ceil(size/stride) output
    positions, total pad ``(out-1)*stride + kernel - size`` (clamped at 0),
    split low-side-first.  A blanket ``kernel - 1`` pad gives wrongly shifted
    (and for some shapes differently-sized) windows whenever stride > 1."""
    out = -(-size // stride)
    pad = max((out - 1) * stride + kernel - size, 0)
    return pad // 2, pad - pad // 2


def im2col_1d(x: Array, kernel: int, stride: int = 1, padding: str = "VALID") -> Array:
    """(..., T, C) -> (..., T', kernel*C) patch extraction."""
    if padding == "SAME":
        lo, hi = _same_pads(x.shape[-2], kernel, stride)
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + [(lo, hi), (0, 0)])
    t = x.shape[-2]
    n_out = (t - kernel) // stride + 1
    idx = jnp.arange(n_out)[:, None] * stride + jnp.arange(kernel)[None, :]
    patches = x[..., idx, :]  # (..., T', K, C)
    return patches.reshape(patches.shape[:-2] + (kernel * x.shape[-1],))


def im2col_2d(x: Array, kernel: Tuple[int, int], stride: Tuple[int, int] = (1, 1),
              padding: str = "VALID") -> Array:
    """(..., H, W, C) -> (..., H', W', kh*kw*C)."""
    kh, kw = kernel
    sh, sw = stride
    if padding == "SAME":
        (hlo, hhi) = _same_pads(x.shape[-3], kh, sh)
        (wlo, whi) = _same_pads(x.shape[-2], kw, sw)
        x = jnp.pad(x, [(0, 0)] * (x.ndim - 3)
                    + [(hlo, hhi), (wlo, whi), (0, 0)])
    hh, ww, c = x.shape[-3], x.shape[-2], x.shape[-1]
    oh = (hh - kh) // sh + 1
    ow = (ww - kw) // sw + 1
    ih = jnp.arange(oh)[:, None] * sh + jnp.arange(kh)[None, :]
    iw = jnp.arange(ow)[:, None] * sw + jnp.arange(kw)[None, :]
    p = x[..., ih[:, None, :, None], iw[None, :, None, :], :]  # (..., oh, ow, kh, kw, C)
    return p.reshape(p.shape[:-3] + (kh * kw * c,))


@dataclasses.dataclass(frozen=True)
class LUTConv1D:
    c_in: int
    c_out: int
    kernel: int
    stride: int = 1
    padding: str = "VALID"
    hidden: int = 8
    n_hidden_layers: int = 1
    activation: str = "tanh"
    use_batchnorm: bool = False
    q_in: QuantConfig = Q_IN_DEFAULT
    q_out: QuantConfig = Q_OUT_DEFAULT
    use_fused: bool = False

    @property
    def dense(self) -> LUTDense:
        return LUTDense(self.c_in * self.kernel, self.c_out, self.hidden,
                        self.n_hidden_layers, self.activation, self.use_batchnorm,
                        self.q_in, self.q_out, use_fused=self.use_fused)

    def init(self, key: Array) -> dict:
        return self.dense.init(key)

    def apply(self, params: dict, x: Array, *, train: bool = False):
        patches = im2col_1d(x, self.kernel, self.stride, self.padding)
        return self.dense.apply(params, patches, train=train)


@dataclasses.dataclass(frozen=True)
class LUTConv2D:
    c_in: int
    c_out: int
    kernel: Tuple[int, int]
    stride: Tuple[int, int] = (1, 1)
    padding: str = "VALID"
    hidden: int = 8
    n_hidden_layers: int = 1
    activation: str = "tanh"
    use_batchnorm: bool = False
    q_in: QuantConfig = Q_IN_DEFAULT
    q_out: QuantConfig = Q_OUT_DEFAULT
    use_fused: bool = False

    @property
    def dense(self) -> LUTDense:
        kh, kw = self.kernel
        return LUTDense(self.c_in * kh * kw, self.c_out, self.hidden,
                        self.n_hidden_layers, self.activation, self.use_batchnorm,
                        self.q_in, self.q_out, use_fused=self.use_fused)

    def init(self, key: Array) -> dict:
        return self.dense.init(key)

    def apply(self, params: dict, x: Array, *, train: bool = False):
        patches = im2col_2d(x, self.kernel, self.stride, self.padding)
        return self.dense.apply(params, patches, train=train)
