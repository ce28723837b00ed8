"""JSC-HLF LUT-Dense stack: how the benchmark builds it, and its plain
reference.  Built through the program's public entry points
(``LUTDense``, ``compile_sequential``, ``make_lut_train_step``)."""

from __future__ import annotations

import jax
import numpy as np

import lutref
import synth
import trainref
import weights


def layers(cfg: dict):
    from repro.core.lut_layers import LUTDense

    d = cfg["dims"]
    return [LUTDense(ci, co, hidden=cfg["hidden"],
                     use_batchnorm=k in cfg["batchnorm_layers"])
            for k, (ci, co) in enumerate(zip(d[:-1], d[1:]))]


def make_weights(cfg: dict, seed: int, *, serve: bool) -> dict:
    d = cfg["dims"]

    def fn(key):
        ks = jax.random.split(key, len(d) - 1)
        return {f"l{k}": weights.lut_dense(ks[k], ci, co, cfg["hidden"],
                                           k in cfg["batchnorm_layers"], serve)
                for k, (ci, co) in enumerate(zip(d[:-1], d[1:]))}

    return weights.make(fn, seed, 1 if serve else 2)


def lower(cfg: dict, params: dict):
    from repro.core.dais import compile_sequential

    with jax.default_matmul_precision(cfg["matmul_precision"]):
        return compile_sequential(layers(cfg),
                                  [params[f"l{k}"] for k in range(len(params))],
                                  cfg["input_f"], cfg["input_i"])


def request_codes(cfg: dict, seed: int, n: int) -> np.ndarray:
    x, _ = synth.jsc_hlf(seed, n)
    return synth.quantize(x, cfg["input_f"], cfg["input_i"], True)


class Reference:
    """Admitted output codes ``(lo, hi)`` of a batch of input codes."""

    def __init__(self, cfg: dict, params: dict, dtype=np.float64):
        host = weights.to_host(params)
        self.layers = [lutref.LutDenseRef(host[f"l{k}"], dtype)
                       for k in range(len(host))]
        self.input_f = cfg["input_f"]

    def __call__(self, codes: np.ndarray):
        lo = hi = np.asarray(codes, np.int64)
        f = self.input_f
        for layer in self.layers:
            lo, hi = layer.apply(lo, hi, f)
            f = layer.F
        return lo, hi


def train_data(cfg: dict, seed: int, n: int):
    """Features quantized onto the input grid, as the paper's data loader
    does, and labels."""
    x, y = synth.jsc_hlf(seed, n)
    codes = synth.quantize(x, cfg["input_f"], cfg["input_i"], True)
    return (codes * 2.0 ** -cfg["input_f"]).astype(np.float32), y


def train_reference(cfg: dict, params0: dict, batches, beta: dict, adam: dict,
                    dtype=np.float32):
    return trainref.run(params0, batches, cfg["batchnorm_layers"], beta, adam,
                        dtype)
