"""Step factories: sharded train / prefill / decode steps for any arch config.

``make_train_step`` builds the full β-regularised HGQ-LUT objective
(CE + β(step)·EBOPs + λ·MoE-aux), takes grads, clips, Adam-updates — all as
one pjit-able function whose in/out shardings are derived from the model's
PDefs (parallel/sharding.py).  The same factory serves the real training
examples (CPU, 1 device) and the 512-device multi-pod dry-run: nothing in
here knows the mesh size.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.ebops import BetaSchedule
from repro.nn.params import init_params
from repro.optim.adam import AdamConfig, adam_init, adam_update
from repro.parallel import sharding as shd


@dataclasses.dataclass(frozen=True)
class TrainHParams:
    adam: AdamConfig = AdamConfig()
    beta: BetaSchedule = BetaSchedule(beta_init=0.0, beta_final=None)
    moe_aux_coef: float = 0.01
    lr_schedule: Optional[Callable] = None
    # Route LUT layers through the fused Pallas fwd+bwd pair (kernels/) so the
    # whole train step runs kernel-side with no (B, C_in, H, C_out) HBM
    # intermediate.  Mirrors ArchConfig.lut_use_fused (configs/base.py);
    # consumed by make_lut_train_step.
    lut_use_fused: bool = False


# --------------------------------------------------------------- shardings
def batch_shardings(model, seq: int, batch: int, mode: str, mesh: Mesh):
    specs = {}
    for k, v in model.input_specs(seq, batch, mode).items():
        spec = shd.batch_dim_spec(v.shape[0], mesh)
        specs[k] = NamedSharding(mesh, P(spec, *([None] * (len(v.shape) - 1))))
    return specs


def param_shardings(model, mesh: Mesh, serve: bool = False):
    fsdp = model.cfg.fsdp
    if serve and model.cfg.serve_fsdp >= 0:
        fsdp = bool(model.cfg.serve_fsdp)
    return shd.param_shardings(model.defs(), mesh, fsdp=fsdp)


def opt_shardings(model, mesh: Mesh):
    ps = param_shardings(model, mesh)
    return {"m": ps, "v": ps,
            "step": NamedSharding(mesh, P())}


def cache_shardings(model, batch: int, t: int, mesh: Mesh):
    return shd.param_shardings(model.cache_defs(batch, t), mesh,
                               fsdp=model.cfg.fsdp)


# -------------------------------------------------------------- train step
def make_train_step(model, mesh: Optional[Mesh] = None,
                    hp: TrainHParams = TrainHParams(),
                    donate: bool = True, batch_shards=None, jit: bool = True):
    """Returns (step_fn, shardings dict).  step_fn(params, opt, batch).

    With ``jit=False`` the *raw* (un-jitted) step function is returned —
    the building block the scan-chunked driver (``train/loop.py``) wraps
    into one jitted K-step ``lax.scan``; raw steps are single-device only
    (a mesh implies pjit, which implies jit).
    """

    def step_fn(params, opt_state, batch):
        step = opt_state["step"]

        def loss_fn(p):
            ce, metrics = model.loss(p, batch)
            beta = hp.beta(step)
            total = (ce + beta * metrics["ebops"]
                     + hp.moe_aux_coef * metrics["aux_loss"])
            return total, metrics

        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state, opt_metrics = adam_update(
            params, grads, opt_state, hp.adam, hp.lr_schedule)
        metrics = {**metrics, **opt_metrics, "loss": loss}
        return params, opt_state, metrics

    if not jit:
        if mesh is not None:
            raise ValueError("jit=False returns the raw step for the chunked "
                             "driver; a mesh requires the jitted/pjit path")
        return step_fn, None
    if mesh is None:
        return jax.jit(step_fn, donate_argnums=(0, 1) if donate else ()), None

    ps = param_shardings(model, mesh)
    os_ = opt_shardings(model, mesh)
    rep = NamedSharding(mesh, P())
    jitted = jax.jit(
        step_fn,
        in_shardings=(ps, os_, batch_shards),
        out_shardings=(ps, os_, None),
        donate_argnums=(0, 1) if donate else (),
    )
    return jitted, {"params": ps, "opt": os_}


def hparams_from_cfg(cfg, **overrides) -> TrainHParams:
    """Seed :class:`TrainHParams` from an :class:`ArchConfig` — the bridge
    that makes config-level knobs (currently ``lut_use_fused``, incl. its
    ``REPRO_LUT_USE_FUSED`` env override) reach the train step."""
    overrides.setdefault("lut_use_fused", getattr(cfg, "lut_use_fused", False))
    return TrainHParams(**overrides)


# ------------------------------------------------------ LUT-stack train step
def make_lut_train_step(layers, hp: TrainHParams = TrainHParams(),
                        donate: bool = True, jit: bool = True):
    """CE + β·EBOPs train step over a stack of LUT layers (the paper-task
    counterpart of :func:`make_train_step`).

    With ``hp.lut_use_fused`` every layer is rerouted through the fused
    Pallas forward + recompute backward (kernels/lut_dense*.py), so one
    training step runs entirely kernel-side.  Returns ``(step_fn, init_fn)``;
    ``step_fn(params, opt_state, batch)`` with ``batch = {"x", "y"}``.
    ``jit=False`` returns the raw step for the scan-chunked driver
    (``train/loop.py``) — β/lr schedules thread through ``opt_state["step"]``,
    so the same function is scanned without extra plumbing.
    """
    from repro.nn.base import merge_aux, scoped_updates

    if hp.lut_use_fused:
        layers = [dataclasses.replace(l, use_fused=True) for l in layers]

    def step_fn(params, opt_state, batch):
        step = opt_state["step"]
        x, y = batch["x"], batch["y"]

        def loss_fn(ps):
            h = x
            auxes = []
            for idx, l in enumerate(layers):
                # the layer's ops carry this scope in the program's metadata
                with jax.named_scope(f"l{idx}_{type(l).__name__}"):
                    h, a = l.apply(ps[f"l{idx}"], h, train=True)
                auxes.append(scoped_updates(f"l{idx}", a))
            aux = merge_aux(*auxes)
            ce = -jnp.mean(jax.nn.log_softmax(h)[jnp.arange(h.shape[0]), y])
            total = ce + hp.beta(step) * aux.ebops + hp.moe_aux_coef * aux.aux_loss
            return total, (ce, aux)

        (loss, (ce, aux)), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        params, opt_state, om = adam_update(params, grads, opt_state,
                                            hp.adam, hp.lr_schedule)
        for path, val in aux.updates.items():   # BN moving stats
            scope, key = path.split("/", 1)
            params[scope][key] = val
        metrics = {"loss": loss, "ce": ce, "ebops": aux.ebops, **om}
        return params, opt_state, metrics

    def init_fn(key):
        ks = jax.random.split(key, len(layers))
        params = {f"l{idx}": l.init(k)
                  for idx, (l, k) in enumerate(zip(layers, ks))}
        return params, adam_init(params)

    if not jit:
        return step_fn, init_fn
    return jax.jit(step_fn, donate_argnums=(0, 1) if donate else ()), init_fn


# -------------------------------------------------------------- serve steps
def make_prefill(model, mesh: Optional[Mesh] = None, batch_shards=None):
    fn = lambda params, batch: model.prefill(params, batch)
    if mesh is None:
        return jax.jit(fn)
    ps = param_shardings(model, mesh, serve=True)
    return jax.jit(fn, in_shardings=(ps, batch_shards))


def make_decode_step(model, batch: int, t: int, mesh: Optional[Mesh] = None):
    fn = lambda params, cache, tokens: model.decode_step(params, cache, tokens)
    if mesh is None:
        return jax.jit(fn, donate_argnums=(1,))
    ps = param_shardings(model, mesh, serve=True)
    cs = cache_shardings(model, batch, t, mesh)
    bspec = shd.batch_dim_spec(batch, mesh)
    toks = NamedSharding(mesh, P(bspec))
    logits = NamedSharding(mesh, P(bspec, None))
    return jax.jit(fn, in_shardings=(ps, cs, toks),
                   out_shardings=(logits, cs), donate_argnums=(1,))


# --------------------------------------------------------------- init utils
def init_state(model, key, mesh: Optional[Mesh] = None):
    """Materialise params + opt state (sharded if mesh given)."""
    defs = model.defs()
    if mesh is None:
        params = init_params(defs, key)
        return params, adam_init(params)
    ps = shd.param_shardings(defs, mesh, fsdp=model.cfg.fsdp)
    init_fn = jax.jit(lambda k: init_params(defs, k), out_shardings=ps)
    params = init_fn(key)
    opt = jax.jit(adam_init, out_shardings=opt_shardings(model, mesh))(params)
    return params, opt
