"""Paper Table III: PLF-JSC LUT-GNN + TGC muon-tracking hybrid.

* PLF: JEDI-Linear-style permutation-invariant network with the paper's
  substitution — EinsumDense → LUT-Dense (per-particle encoder + sum pool +
  LUT-Dense classifier head), hidden dim 8 as in §V-D: the canonical
  ``repro.models.plf`` list, trained on the normal path.
* TGC: hybrid per §V-E — HGQ (matmul) feature extractor + LUT-Dense output
  head, regression target in mrad; metric is angular resolution (RMS).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.ebops import BetaSchedule, estimate_luts
from repro.core.hgq_layers import HGQDense
from repro.core.lut_layers import LUTDense
from repro.core.quant import int_to_float, quantize_to_int
from repro.data.synthetic import jsc_plf, tgc_muon
from repro.models.plf import IN_F, IN_I, build_plf_layers
from repro.nn.base import merge_aux
from repro.optim.adam import AdamConfig, adam_init, adam_update, cosine_restarts
from repro.train.loop import run_chunked
from repro.train.steps import TrainHParams, make_lut_train_step


def run_plf(steps: int = 400, batch: int = 512, n_train: int = 8000,
            n_test: int = 2000) -> None:
    """The canonical model (``repro.models.plf``) at the generator's 32
    particles of 16 features, trained through ``make_lut_train_step``
    under ``train/loop.chunked_train``."""
    xtr, ytr = jsc_plf(0, n_train, split="train")
    xte, yte = jsc_plf(0, n_test, split="test")
    q = lambda x: int_to_float(quantize_to_int(x, IN_F, IN_I, True, "SAT"),
                               IN_F).astype(np.float32)
    xtr, xte = q(xtr), q(xte)

    layers = build_plf_layers()        # encoder, set sum, head; hidden 8
    hp = TrainHParams(adam=AdamConfig(lr=3e-3),
                      beta=BetaSchedule(1e-7, None),
                      lr_schedule=cosine_restarts(3e-3, first_period=200,
                                                  warmup=20))
    step, init = make_lut_train_step(layers, hp, jit=False)
    params, opt = init(jax.random.PRNGKey(0))
    idx = np.random.default_rng(0).integers(0, len(xtr), (steps, batch))
    get = lambda s: {"x": xtr[idx[s]], "y": ytr[idx[s]]}

    t0 = time.time()
    params, opt, _ = run_chunked(step, params, opt, get, 0, steps,
                                 chunk_steps=50)
    us = (time.time() - t0) / steps * 1e6
    h, auxes = jnp.asarray(xte), []
    for k, layer in enumerate(layers):
        h, a = layer.apply(params[f"l{k}"], h)
        auxes.append(a)
    acc = float(jnp.mean(jnp.argmax(h, -1) == jnp.asarray(yte)))
    eb = float(merge_aux(*auxes).ebops)
    emit("table3/plf_lut_gnn", us,
         f"acc={acc:.4f};ebops={eb:.0f};est_luts={estimate_luts(eb):.0f}")


def run_tgc() -> None:
    xtr, atr = tgc_muon(0, 12000, "train")
    xte, ate = tgc_muon(0, 3000, "test")

    feat1 = HGQDense(350, 32, activation="relu")
    feat2 = HGQDense(32, 16, activation="relu")
    head = LUTDense(16, 1, hidden=8)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    params = {"f1": feat1.init(ks[0]), "f2": feat2.init(ks[1]),
              "h": head.init(ks[2])}
    opt = adam_init(params)
    acfg = AdamConfig(lr=1e-3)
    sched = cosine_restarts(1e-3, first_period=300, warmup=20)

    def fwd(p, x, train):
        z, a1 = feat1.apply(p["f1"], x, train=train)
        z, a2 = feat2.apply(p["f2"], z, train=train)
        pred, a3 = head.apply(p["h"], z, train=train)
        return pred[:, 0] * 30.0, merge_aux(a1, a2, a3)

    @jax.jit
    def step(params, opt, x, a):
        def loss_fn(p):
            pred, aux = fwd(p, x, True)
            return jnp.mean((pred - a) ** 2) / 900.0 + 2e-8 * aux.ebops, aux
        (_, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt, _ = adam_update(params, g, opt, acfg, sched)
        return params, opt, aux.ebops

    rng = np.random.default_rng(0)
    t0 = time.time()
    for s in range(600):
        idx = rng.integers(0, len(xtr), 512)
        params, opt, ebops = step(params, opt, jnp.asarray(xtr[idx]),
                                  jnp.asarray(atr[idx]))
    us = (time.time() - t0) / 600 * 1e6
    pred, aux = fwd(params, jnp.asarray(xte), False)
    res = float(jnp.sqrt(jnp.mean((pred - jnp.asarray(ate)) ** 2)))
    eb = float(aux.ebops)
    emit("table3/tgc_hybrid", us,
         f"resolution_mrad={res:.3f};ebops={eb:.0f};"
         f"est_luts={estimate_luts(eb):.0f}")


def run() -> None:
    run_plf()
    run_tgc()
