"""Plain reference for served LUT networks: what each output code must be.

It follows the paper's deployed semantics (HGQ-LUT, sections III-IV) and
imports nothing of the program.  Values are integer codes on fixed-point
grids: code ``c`` on grid ``f`` is the value ``c * 2**-f``.

- A requantization onto grid ``(f, i)`` rounds half to even and then
  saturates (SAT) or wraps (WRAP) to ``f + i + sign`` bits; a width of 0
  or less prunes the value to 0.
- An L-LUT cell ``(j, i)`` of a LUT-Dense layer requantizes input ``j``
  onto its own WRAP grid ``(f_in, i_in)``, evaluates its MLP
  ``sum_h tanh(x * w0 + b0) * w_out + b_out`` on that value, applies the
  folded batch-norm ``y * inv + (bias - mean * inv)`` with
  ``inv = scale / sqrt(var + 1e-5)``, and quantizes onto its SAT output
  grid ``(f_out, i_out)``.  Output ``i`` is the sum over ``j`` of the cell
  codes, each aligned to the finest grid of the live cells of the layer.
- An HGQ dense layer requantizes its inputs onto ``q_a`` (SAT), multiplies
  by the weights quantized onto ``q_w`` (SAT), adds the bias rounded onto
  the product grid, and clamps at 0 for relu.  It is exact in integers.

Each cell's MLP is evaluated in float64 at every code of its input grid
(its truth table).  The program evaluates it in float32, so a value that
lies within ``EPS`` of a rounding boundary, relative to the size of the
terms that make it, may round either way: the reference then admits both
codes, and carries the admitted range ``[lo, hi]`` through every later
layer.  A served output is right when it lies inside its range.
"""

from __future__ import annotations

import numpy as np

# Admitted float error, relative to the magnitude of the terms that make a
# cell's value (each hidden unit's output and, through the slope of tanh,
# its argument): float32 arithmetic errs by about 2**-20 of that over the
# ~20 operations of a cell; 2**-16 leaves a margin of 16, and is 256 times
# below the error of bfloat16 arithmetic (2**-8), which the control uses.
EPS = 2.0 ** -16
BN_EPS = 1e-5


def requant(c: np.ndarray, src_f, f, i, signed: bool, mode: str) -> np.ndarray:
    """Integer codes on grid ``src_f`` -> codes on grid ``(f, i)``."""
    c = np.asarray(c, np.int64)
    shift = np.asarray(f, np.int64) - np.asarray(src_f, np.int64)
    code = np.round(c * np.exp2(shift.astype(np.float64))).astype(np.int64)
    return wrap_or_sat(code, f + np.asarray(i) + (1 if signed else 0),
                       signed, mode)


def wrap_or_sat(code: np.ndarray, width, signed: bool, mode: str) -> np.ndarray:
    width = np.asarray(width, np.int64)
    n = np.where(width > 0, np.left_shift(1, np.maximum(width, 0)), 1)
    lo = -(n // 2) if signed else np.zeros_like(n)
    hi = lo + n - 1
    out = np.clip(code, lo, hi) if mode == "SAT" else lo + np.mod(code - lo, n)
    return np.where(width > 0, out, 0)


def _as(x, dtype):
    return np.asarray(x, np.float64).astype(dtype)


class LutDenseRef:
    """One LUT-Dense layer (or a LUT-Conv's dense cell grid) from its
    float parameters.  ``dtype`` is the arithmetic of the MLP: float64 for
    the reference, bfloat16 for the control (then nothing is admitted
    twice)."""

    def __init__(self, p: dict, dtype=np.float64):
        self.f_in = np.round(np.asarray(p["q_in"]["f"])).astype(np.int64)
        self.i_in = np.round(np.asarray(p["q_in"]["i"])).astype(np.int64)
        self.f_out = np.round(np.asarray(p["q_out"]["f"])).astype(np.int64)
        self.i_out = np.round(np.asarray(p["q_out"]["i"])).astype(np.int64)
        self.m = np.maximum(self.f_in + self.i_in + 1, 0)
        self.n = np.maximum(self.f_out + self.i_out + 1, 0)
        self.live = (self.m > 0) & (self.n > 0)
        self.F = int(self.f_out[self.live].max()) if self.live.any() else 0
        self.align = np.maximum(self.F - self.f_out, 0)
        self.lo_tab, self.hi_tab = self._tables(p, dtype)

    def _tables(self, p: dict, dtype):
        ci, co = self.f_in.shape
        e = np.arange(1 << int(self.m.max()), dtype=np.int64)[:, None, None]
        size = np.left_shift(1, np.maximum(self.m, 1))[None]
        code = np.mod(e, size)
        code = np.where(code >= size // 2, code - size, code)
        x = _as(code * np.exp2(-self.f_in.astype(np.float64))[None], dtype)
        w0, b0, wo = (_as(p[k], dtype) for k in ("w0", "b0", "w_out"))
        h = np.tanh(x[..., None] * w0[None] + b0[None])          # (E,ci,co,H)
        y = np.sum(h * wo[None], axis=-1) + _as(p["b_out"], dtype)[None]
        h64 = h.astype(np.float64)
        arg = (np.abs(x[..., None].astype(np.float64) * np.asarray(p["w0"], np.float64))
               + np.abs(np.asarray(p["b0"], np.float64)))
        mag = (np.sum((np.abs(h64) + (1.0 - h64 ** 2) * arg)
                      * np.abs(np.asarray(p["w_out"], np.float64)), axis=-1)
               + np.abs(np.asarray(p["b_out"], np.float64)))
        if "bn_scale" in p:
            inv = _as(np.asarray(p["bn_scale"], np.float64)
                      / np.sqrt(np.asarray(p["bn_var"], np.float64) + BN_EPS),
                      dtype)
            shift = _as(p["bn_bias"], dtype) - _as(p["bn_mean"], dtype) * inv
            y = y * inv[None] + shift[None]
            mag = (mag * np.abs(inv.astype(np.float64))
                   + np.abs(shift.astype(np.float64)))
        v = y.astype(np.float64) * np.exp2(self.f_out)[None]
        near = np.floor(v) + 0.5
        tie = np.abs(v - near) <= EPS * mag * np.exp2(self.f_out)[None]
        if dtype != np.float64:
            tie[:] = False
        lo = np.where(tie, np.floor(v), np.round(v)).astype(np.int64)
        hi = np.where(tie, np.floor(v) + 1, np.round(v)).astype(np.int64)
        lo, hi = (np.where(self.live[None],
                           wrap_or_sat(t, self.n[None], True, "SAT"), 0)
                  for t in (lo, hi))
        return lo.transpose(1, 2, 0), hi.transpose(1, 2, 0)      # (ci,co,E)

    def apply(self, lo: np.ndarray, hi: np.ndarray, src_f):
        """Admitted input codes ``[lo, hi]`` (R, ci) on grids ``src_f``
        (ci,) -> admitted output codes (R, co) on grid ``self.F``."""
        ci, co = self.f_in.shape
        src_f = np.broadcast_to(np.asarray(src_f, np.int64), (ci,))
        scale = np.exp2((self.f_in - src_f[:, None]).astype(np.float64))
        r_lo = np.round(lo[..., None] * scale).astype(np.int64)  # (R,ci,co)
        r_hi = np.round(hi[..., None] * scale).astype(np.int64)
        size = np.left_shift(1, self.m)
        span = np.minimum(r_hi - r_lo, size - 1)
        jj = np.arange(ci)[:, None]
        ii = np.arange(co)[None, :]
        out_lo = self.lo_tab[jj, ii, np.mod(r_lo, size)]
        out_hi = self.hi_tab[jj, ii, np.mod(r_lo, size)]
        for k in range(1, int(span.max(initial=0)) + 1):
            idx = np.mod(r_lo + k, size)
            more = span >= k
            out_lo = np.where(more, np.minimum(out_lo, self.lo_tab[jj, ii, idx]),
                              out_lo)
            out_hi = np.where(more, np.maximum(out_hi, self.hi_tab[jj, ii, idx]),
                              out_hi)
        w = np.left_shift(1, self.align)
        live = self.live
        out_lo = np.where(live, out_lo * w, 0).sum(axis=-2)
        out_hi = np.where(live, out_hi * w, 0).sum(axis=-2)
        return out_lo, out_hi


class HgqDenseRef:
    """An HGQ dense layer on integer codes; exact, so ``lo == hi`` maps
    through unchanged."""

    def __init__(self, p: dict, relu: bool):
        self.fa = np.round(np.asarray(p["q_a"]["f"])).astype(np.int64)
        self.ia = np.round(np.asarray(p["q_a"]["i"])).astype(np.int64)
        fw = np.round(np.asarray(p["q_w"]["f"])).astype(np.int64)
        iw = np.round(np.asarray(p["q_w"]["i"])).astype(np.int64)
        w = np.asarray(p["w"], np.float64)
        self.w_codes = wrap_or_sat(np.round(w * np.exp2(fw)).astype(np.int64),
                                   fw + iw + 1, True, "SAT")
        c_in = w.shape[0]
        self.fa = np.broadcast_to(self.fa, (c_in,))
        self.ia = np.broadcast_to(self.ia, (c_in,))
        fprod = fw + self.fa[:, None]
        self.F = fprod.max(axis=0)                               # (co,)
        self.align = np.left_shift(1, self.F[None] - fprod)
        b = np.asarray(p.get("b", np.zeros(w.shape[1])), np.float64)
        self.b_codes = np.round(b * np.exp2(self.F)).astype(np.int64)
        self.relu = relu

    def apply(self, codes: np.ndarray, src_f) -> np.ndarray:
        a = requant(codes, src_f, self.fa, self.ia, True, "SAT")  # (R, ci)
        out = a @ (self.w_codes * self.align) + self.b_codes
        return np.maximum(out, 0) if self.relu else out


def patches_1d(x: np.ndarray, kernel: int, stride: int, padding: str
               ) -> np.ndarray:
    """(R, T, C) -> (R, S, kernel*C) patches, kernel-major; SAME pads with
    code 0, split low side first."""
    t = x.shape[1]
    if padding == "SAME":
        out = -(-t // stride)
        pad = max((out - 1) * stride + kernel - t, 0)
        x = np.pad(x, ((0, 0), (pad // 2, pad - pad // 2), (0, 0)))
    n_out = (x.shape[1] - kernel) // stride + 1
    idx = np.arange(n_out)[:, None] * stride + np.arange(kernel)[None, :]
    p = x[:, idx, :]
    return p.reshape(x.shape[0], n_out, kernel * x.shape[2])
