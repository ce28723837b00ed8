"""CEPC PID hybrid: how the benchmark builds it, and its plain reference.
Built through the program's public entry points (``models.pid``,
``core.lower.lower``)."""

from __future__ import annotations

import jax
import numpy as np

import lutref
import synth
import weights


def layers(cfg: dict):
    from repro.models.pid import build_pid_layers

    return build_pid_layers(window=cfg["window"], features=cfg["features"],
                            hidden=cfg["hidden"])


def make_weights(cfg: dict, seed: int, *, serve: bool = True) -> dict:
    w, feat, k, h = cfg["window"], cfg["features"], cfg["conv_kernel"], cfg["hidden"]
    c1, c2 = cfg["conv_channels"]
    (fw, iw), (fa, ia) = cfg["front_q_w"], cfg["front_q_a"]

    def fn(key):
        ks = jax.random.split(key, 4)
        return {"front": weights.hgq_dense(ks[0], w, feat, fw, iw, fa, ia),
                "lc1": weights.lut_dense(ks[1], k * feat, c1, h, False, serve),
                "lc2": weights.lut_dense(ks[2], k * c1, c2, h, False, serve),
                "head": weights.lut_dense(ks[3], c2, 1, h, False, serve)}

    return weights.make(fn, seed, 3)


def lower(cfg: dict, params: dict):
    from repro.core.lower import lower as lower_graph
    from repro.models.pid import build_pid_graph

    with jax.default_matmul_precision(cfg["matmul_precision"]):
        graph = build_pid_graph(layers(cfg), n_samples=cfg["n_samples"],
                                in_f=cfg["input_f"], in_i=cfg["input_i"])
        return lower_graph(graph, [params["front"], params["lc1"],
                                   params["lc2"], params["head"], None])


def request_codes(cfg: dict, seed: int, n: int) -> np.ndarray:
    wf, _ = synth.cepc_waveform(seed, n, cfg["n_samples"])
    return synth.quantize(wf, cfg["input_f"], cfg["input_i"], False)


class Reference:
    """Admitted output codes ``(lo, hi)`` of a batch of waveforms."""

    BLOCK = 64      # waveforms per pass: bounds the host memory it takes

    def __init__(self, cfg: dict, params: dict, dtype=np.float64):
        host = weights.to_host(params)
        self.cfg = cfg
        self.front = lutref.HgqDenseRef(host["front"], relu=True)
        self.lc = [lutref.LutDenseRef(host[k], dtype) for k in ("lc1", "lc2")]
        self.head = lutref.LutDenseRef(host["head"], dtype)

    def __call__(self, codes: np.ndarray):
        out = [self._block(codes[s:s + self.BLOCK])
               for s in range(0, len(codes), self.BLOCK)]
        return (np.concatenate([o[0] for o in out]),
                np.concatenate([o[1] for o in out]))

    def _block(self, codes: np.ndarray):
        cfg, r = self.cfg, codes.shape[0]
        w, k = cfg["window"], cfg["conv_kernel"]
        x = np.asarray(codes, np.int64)[..., None]
        p = lutref.patches_1d(x, w, w, "VALID")                 # (R, S, w)
        s = p.shape[1]
        a = self.front.apply(p.reshape(r * s, w), cfg["input_f"])
        lo = hi = a.reshape(r, s, -1)
        f = self.front.F
        for layer in self.lc:
            src_f = np.tile(f, k)
            lo = lutref.patches_1d(lo, k, 1, "SAME")
            hi = lutref.patches_1d(hi, k, 1, "SAME")
            lo, hi = layer.apply(lo.reshape(r * s, -1), hi.reshape(r * s, -1),
                                 src_f)
            lo, hi = lo.reshape(r, s, -1), hi.reshape(r, s, -1)
            f = np.full(lo.shape[-1], layer.F)
        lo, hi = self.head.apply(lo.reshape(r * s, -1), hi.reshape(r * s, -1),
                                 f)
        return lo.reshape(r, s, -1).sum(axis=1), hi.reshape(r, s, -1).sum(axis=1)
