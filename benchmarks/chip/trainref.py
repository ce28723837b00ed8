"""Plain reference for training a LUT-Dense stack (HGQ-LUT, section III).

Written from the method's description in straightforward ``jax.numpy``
and float32 at the highest matmul precision, importing nothing of the
program:

- every cell ``(j, i)`` fake-quantizes its input with WRAP onto
  ``(f_in, i_in)``, evaluates ``sum_h tanh(x * w0 + b0) * w_out + b_out``,
  applies train-mode batch-norm where the layer has it (batch statistics;
  moving statistics updated with momentum 0.99), fake-quantizes with SAT
  onto ``(f_out, i_out)``; outputs sum over ``j``;
- bit widths are the clipped parameters rounded with a straight-through
  gradient; the fake-quantizer passes the gradient straight through for
  the value (SAT: inside the range only) and gives the bit widths HGQ's
  surrogate: ``ln2 * (x - round(x))`` for ``f`` inside the range,
  ``ln2 * 2**-f`` for ``f`` and ``ln2 * 2**i`` for ``i`` above it,
  ``-ln2 * 2**i`` for ``i`` below it, nothing for ``i`` under WRAP;
- the loss is softmax cross-entropy plus ``beta(step)`` times the LUT EBOPs
  of every cell (Eq. 5, LUT-6 split into LUT-5s), ``beta`` ramped
  log-linearly;
- Adam with global-norm clipping and bias correction; the moving
  batch-norm statistics replace their parameters after the update.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN2 = math.log(2.0)
BIT_MIN, BIT_MAX = -8.0, 12.0
BN_EPS, BN_MOMENTUM = 1e-5, 0.99
LUT_X, LUT_Y = 6, 5


def _bits(p):
    c = jnp.clip(p, BIT_MIN, BIT_MAX)
    return c + jax.lax.stop_gradient(jnp.round(c) - c)


def _fq_value(x, f, i, sat: bool):
    scale = jnp.exp2(-f)
    hi = jnp.exp2(i) - scale
    lo = -jnp.exp2(i)
    q = jnp.round(x / scale) * scale
    q = jnp.clip(q, lo, hi) if sat else lo + jnp.mod(q - lo, hi - lo + scale)
    return jnp.where(i + f + 1.0 > 0.0, q, 0.0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def fake_quant(x, f, i, sat: bool):
    return _fq_value(x, f, i, sat)


def _fq_fwd(x, f, i, sat):
    return _fq_value(x, f, i, sat), (x, f, i)


def _fq_bwd(sat, res, g):
    x, f, i = res
    scale = jnp.exp2(-f)
    rounded = jnp.round(x / scale) * scale
    alive = i + f + 1.0 > 0.0
    if sat:
        above = rounded > jnp.exp2(i) - scale
        below = rounded < -jnp.exp2(i)
        dx = jnp.where(alive & ~above & ~below, g, 0.0)
        df = jnp.where(above, LN2 * scale, jnp.where(below, 0.0, LN2 * (x - rounded)))
        di = jnp.where(above, LN2 * jnp.exp2(i), jnp.where(below, -LN2 * jnp.exp2(i), 0.0))
    else:
        dx = jnp.where(alive, g, 0.0)
        df = LN2 * (x - rounded)
        di = jnp.zeros_like(x)
    df = jnp.where(alive, df * g, 0.0)
    di = jnp.where(alive, di * g, 0.0)
    lead = tuple(range(g.ndim - f.ndim))
    return dx, jnp.sum(df, axis=lead), jnp.sum(di, axis=lead)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


def ebops(m, n):
    m, n = jnp.maximum(m, 0.0), jnp.maximum(n, 0.0)
    cost = jnp.where(m >= LUT_Y, jnp.exp2(m - LUT_X) * n,
                     (m / LUT_Y) * 2.0 ** (LUT_Y - LUT_X) * n)
    return jnp.sum(jnp.where((m > 0) & (n > 0), cost, 0.0))


def layer(p: dict, x, bn: bool):
    """One LUT-Dense layer in train mode -> (out, ebops, bn updates)."""
    f_in, i_in = _bits(p["q_in"]["f"]), _bits(p["q_in"]["i"])
    f_out, i_out = _bits(p["q_out"]["f"]), _bits(p["q_out"]["i"])
    xb = jnp.broadcast_to(x[:, :, None], x.shape + (f_in.shape[1],))
    xq = fake_quant(xb, f_in, i_in, False)
    h = jnp.tanh(xq[..., None] * p["w0"] + p["b0"])
    y = jnp.sum(h * p["w_out"], axis=-1) + p["b_out"]
    upd = {}
    if bn:
        mean, var = jnp.mean(y, axis=0), jnp.var(y, axis=0)
        upd = {"bn_mean": BN_MOMENTUM * p["bn_mean"] + (1 - BN_MOMENTUM) * mean,
               "bn_var": BN_MOMENTUM * p["bn_var"] + (1 - BN_MOMENTUM) * var}
        y = (y - mean) * jax.lax.rsqrt(var + BN_EPS) * p["bn_scale"] + p["bn_bias"]
    yq = fake_quant(y, f_out, i_out, True)
    cost = ebops(jnp.maximum(f_in + i_in + 1.0, 0.0),
                 jnp.maximum(f_out + i_out + 1.0, 0.0))
    return jnp.sum(yq, axis=1), cost, jax.lax.stop_gradient(upd)


def make_step(bn_layers, beta: dict, adam: dict, dtype=jnp.float32):
    """Jitted ``step(params, opt, x, y) -> (params, opt, loss, clipped grads)``.

    ``dtype`` is the arithmetic of the loss and its gradient (the control
    runs it in bfloat16); the optimizer works in float32 either way.
    """

    def beta_at(step):
        t = jnp.clip(step.astype(jnp.float32) / max(beta["steps"] - 1, 1), 0.0, 1.0)
        return jnp.exp((1.0 - t) * jnp.log(jnp.float32(beta["init"]))
                       + t * jnp.log(jnp.float32(beta["final"])))

    def loss_fn(params, x, y, step):
        params = jax.tree.map(lambda v: v.astype(dtype), params)
        h, cost, upds = x.astype(dtype), 0.0, {}
        for k in range(len(params)):
            h, c, u = layer(params[f"l{k}"], h, k in bn_layers)
            cost, upds[f"l{k}"] = cost + c, u
        ce = -jnp.mean(jax.nn.log_softmax(h)[jnp.arange(h.shape[0]), y])
        loss = (ce + beta_at(step).astype(dtype) * cost).astype(jnp.float32)
        return loss, jax.tree.map(lambda v: v.astype(jnp.float32), upds)

    def step(params, opt, x, y):
        with jax.default_matmul_precision("highest"):
            (loss, upds), g = jax.value_and_grad(loss_fn, has_aux=True)(
                params, x, y, opt["step"])
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(v)) for v in jax.tree.leaves(g)))
            g = jax.tree.map(lambda v: v * jnp.minimum(1.0, adam["clip_norm"] / (gn + 1e-9)), g)
            t = (opt["step"] + 1).astype(jnp.float32)
            m = jax.tree.map(lambda a, b: adam["b1"] * a + (1 - adam["b1"]) * b, opt["m"], g)
            v = jax.tree.map(lambda a, b: adam["b2"] * a + (1 - adam["b2"]) * b * b, opt["v"], g)
            params = jax.tree.map(
                lambda p, a, b: p - adam["lr"] * (a / (1 - adam["b1"] ** t))
                / (jnp.sqrt(b / (1 - adam["b2"] ** t)) + adam["eps"]),
                params, m, v)
            for scope, u in upds.items():
                params[scope] = {**params[scope], **u}
        return params, {"m": m, "v": v, "step": opt["step"] + 1}, loss, g

    return jax.jit(step)


def run(params, batches, bn_layers, beta: dict, adam: dict, dtype=jnp.float32):
    """Run the reference from ``params`` over ``batches`` [(x, y), ...].

    Returns the loss of each step, the clipped gradient of the first step
    and the parameters after the last, all on the host.
    """
    step = make_step(tuple(bn_layers), beta, adam, dtype)
    params = jax.tree.map(jnp.asarray, params)
    opt = {"m": jax.tree.map(jnp.zeros_like, params),
           "v": jax.tree.map(jnp.zeros_like, params),
           "step": jnp.zeros((), jnp.int32)}
    losses, g1 = [], None
    for x, y in batches:
        params, opt, loss, g = step(params, opt, jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
        if g1 is None:
            g1 = jax.device_get(g)
    return losses, g1, jax.device_get(params)
