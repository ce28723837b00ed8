"""Input synthesis for the chip benchmark: the yardstick's own copies.

Copies of ``repro.data.synthetic.jsc_hlf`` and ``cepc_waveform`` (the
waveform one vectorized: same distributions, not the same draws), kept
here so that a change to the program's generators cannot change what the
benchmark feeds it.  Every function is a pure function of its seed.
"""

from __future__ import annotations

import numpy as np

N_HLF_FEATURES = 16
N_JET_CLASSES = 5


def _rng(seed: int, step: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([seed, step]))


def jsc_hlf(seed: int, n: int) -> tuple[np.ndarray, np.ndarray]:
    """16 jet-substructure-like features and 5 classes (q/g/W/Z/t analogue).

    Class-conditional Gaussians with nonlinear couplings; classes 2 and 3
    share most of their centre, as W and Z do.
    """
    rng = _rng(seed, 0)
    y = rng.integers(0, N_JET_CLASSES, size=n)
    centers = _rng(seed, 99).normal(0, 0.85, size=(N_JET_CLASSES, N_HLF_FEATURES))
    centers[3] = centers[2] + _rng(seed, 98).normal(0, 0.30, N_HLF_FEATURES)
    x = centers[y] + rng.normal(0, 1.0, size=(n, N_HLF_FEATURES))
    x[:, 0] = np.abs(x[:, 0]) + 0.5 * x[:, 1] ** 2
    x[:, 5] = np.tanh(x[:, 5]) * (1 + 0.3 * y)
    x[:, 10] = x[:, 10] * x[:, 11] * 0.5
    return x.astype(np.float32), y.astype(np.int32)


def cepc_waveform(seed: int, n: int, length: int = 3000
                  ) -> tuple[np.ndarray, np.ndarray]:
    """Drift-chamber waveforms: primary-cluster impulse trains on noise.

    Pions (0) and kaons (1) differ in cluster density (0.009 and 0.012
    clusters per sample).  Each cluster adds ``amp * exp(-k/8)`` for
    ``k < 40`` at a position uniform in ``[0, length - 45)``; the noise is
    N(0, 0.05) and the ADC clamps to ``[0, 8 - 2**-9]``.  Returns the
    waveforms ``(n, length)`` float32 and the clusters per waveform.
    """
    rng = _rng(seed, 30)
    species = rng.integers(0, 2, size=n)
    dens = np.where(species == 1, 0.012, 0.009)
    n_cl = rng.poisson(dens * length)
    row = np.repeat(np.arange(n), n_cl)
    pos = rng.integers(0, length - 45, size=row.size)
    amp = rng.uniform(0.4, 1.2, size=row.size)
    wf = rng.standard_normal((n, length), dtype=np.float32) * np.float32(0.05)
    tail = np.exp(-np.arange(40) / 8.0)
    flat = (row * length + pos)[:, None] + np.arange(40)[None, :]
    wf += np.bincount(flat.ravel(), weights=(amp[:, None] * tail).ravel(),
                      minlength=n * length).reshape(n, length).astype(np.float32)
    np.clip(wf, 0.0, 8.0 - 2 ** -9, out=wf)
    return wf, n_cl


def quantize(x: np.ndarray, f: int, i: int, signed: bool) -> np.ndarray:
    """Float values -> int64 codes on the (f, i) grid, round half to even,
    saturating: the input grid a client quantizes to before a request."""
    width = f + i + (1 if signed else 0)
    lo = -(1 << (width - 1)) if signed else 0
    hi = lo + (1 << width) - 1
    return np.clip(np.round(np.asarray(x, np.float64) * 2.0 ** f),
                   lo, hi).astype(np.int64)
