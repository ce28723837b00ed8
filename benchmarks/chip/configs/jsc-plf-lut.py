"""JSC-PLF particle-list tagger: how the benchmark builds it, and its plain
reference.  Built through the program's public entry points
(``models.plf``, ``core.lower.lower``)."""

from __future__ import annotations

import jax
import numpy as np

import lutref
import synth
import weights


def layers(cfg: dict):
    from repro.models.plf import build_plf_layers

    return build_plf_layers(n_features=cfg["n_features"], width=cfg["width"],
                            hidden=cfg["hidden"], n_classes=cfg["n_classes"])


def make_weights(cfg: dict, seed: int, *, serve: bool = True) -> dict:
    f, w, h, c = cfg["n_features"], cfg["width"], cfg["hidden"], cfg["n_classes"]

    def fn(key):
        ks = jax.random.split(key, 2)
        return {"enc": weights.lut_dense(ks[0], f, w, h, True, serve),
                "head": weights.lut_dense(ks[1], w, c, h, False, serve)}

    return weights.make(fn, seed, 4)


def lower(cfg: dict, params: dict):
    from repro.core.lower import lower as lower_graph
    from repro.models.plf import build_plf_graph

    with jax.default_matmul_precision(cfg["matmul_precision"]):
        graph = build_plf_graph(layers(cfg), n_particles=cfg["n_particles"],
                                in_f=cfg["input_f"], in_i=cfg["input_i"])
        return lower_graph(graph, [params["enc"], None, params["head"]])


def jsc_plf(seed: int, n: int, n_particles: int, n_features: int) -> np.ndarray:
    """The yardstick's copy of ``repro.data.synthetic.jsc_plf``: (n, P, F)
    zero-padded particle lists with class-dependent (pT, eta, phi) shapes,
    particles ordered by falling pT, padded slots last."""
    rng = synth._rng(seed, 10)
    y = rng.integers(0, synth.N_JET_CLASSES, size=n)
    n_real = rng.integers(n_particles // 4, n_particles + 1, size=n)
    pt = rng.exponential(1.0 + 0.4 * y[:, None], size=(n, n_particles))
    width = 0.3 + 0.15 * (y[:, None] % 3)
    eta = rng.normal(0, width, size=(n, n_particles))
    phi = rng.normal(0, width, size=(n, n_particles))
    extra = rng.normal(0, 1, size=(n, n_particles, max(n_features - 3, 0)))
    extra[..., 0::2] *= (0.5 + 0.2 * y[:, None, None])
    x = np.concatenate([np.stack([pt, eta, phi], -1), extra],
                       axis=-1)[:, :, :n_features]
    mask = np.arange(n_particles)[None, :] < n_real[:, None]
    x = np.where(mask[..., None], x, 0.0)
    order = np.argsort(-np.where(mask, pt, -1.0), axis=1)
    return np.take_along_axis(x, order[..., None], axis=1).astype(np.float32)


def request_codes(cfg: dict, seed: int, n: int) -> np.ndarray:
    x = jsc_plf(seed, n, cfg["n_particles"], cfg["n_features"])
    codes = synth.quantize(x, cfg["input_f"], cfg["input_i"], cfg["input_signed"])
    return codes.reshape(n, -1)


class Reference:
    """Admitted output codes ``(lo, hi)`` of a batch of events: the encoder
    on every particle (``lutref.LutDenseRef``, float64), the exact integer
    sum of each channel's admitted codes over the particles, the head."""

    BLOCK = 256     # events per pass: bounds the host memory it takes

    def __init__(self, cfg: dict, params: dict, dtype=np.float64):
        host = weights.to_host(params)
        self.cfg = cfg
        self.enc = lutref.LutDenseRef(host["enc"], dtype)
        self.head = lutref.LutDenseRef(host["head"], dtype)

    def __call__(self, codes: np.ndarray):
        out = [self._block(codes[s:s + self.BLOCK])
               for s in range(0, len(codes), self.BLOCK)]
        return (np.concatenate([o[0] for o in out]),
                np.concatenate([o[1] for o in out]))

    def _block(self, codes: np.ndarray):
        cfg, r = self.cfg, codes.shape[0]
        x = np.asarray(codes, np.int64).reshape(r * cfg["n_particles"], -1)
        lo, hi = self.enc.apply(x, x, cfg["input_f"])
        lo = lo.reshape(r, cfg["n_particles"], -1).sum(axis=1)
        hi = hi.reshape(r, cfg["n_particles"], -1).sum(axis=1)
        return self.head.apply(lo, hi, self.enc.F)
