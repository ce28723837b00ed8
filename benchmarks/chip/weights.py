"""Weights from a seed, made on the device in one jitted call.

The layouts are those the program's layers take (``LUTDense``,
``HGQDense`` parameter dicts); the values are drawn here, so the plain
reference takes nothing that the program made.  ``serve=True`` draws
what a trained network looks like at deployment (per-cell bit widths from
a fixed set, batch-norm statistics, output biases); ``serve=False`` draws the
untrained state that training starts from.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def key_for(seed: int, tag: int) -> jax.Array:
    """A PRNG key from any non-negative seed, 64 bits and beyond."""
    word = np.random.SeedSequence([int(seed), tag]).generate_state(1)[0]
    return jax.random.PRNGKey(int(word))


def _widths(key, shape) -> dict:
    """Per-cell bit widths of a deployed LUT layer: a fixed set of
    (f_in, i_in, f_out, i_out) tuples, f_in and i_in in 2..4, f_out in
    3..5, i_out in 2..3, dealt to the cells in an order drawn from the seed.
    Every seed gets the same table sizes, and so the same work."""
    c = jnp.arange(shape[0] * shape[1])
    cols = {"f_in": 2 + c % 3, "i_in": 2 + c // 3 % 3,
            "f_out": 3 + c // 9 % 3, "i_out": 2 + c // 27 % 2}
    order = jax.random.permutation(key, c.size)
    return {k: v[order].reshape(shape).astype(jnp.float32) for k, v in cols.items()}


def lut_dense(key, c_in: int, c_out: int, hidden: int, bn: bool,
              serve: bool) -> dict:
    ks = jax.random.split(key, 12)
    shape = (c_in, c_out)
    n = lambda k, s: jax.random.normal(k, s, jnp.float32)
    p = {"w0": n(ks[0], shape + (hidden,)),
         "b0": n(ks[1], shape + (hidden,)) * 0.5,
         "w_out": n(ks[2], shape + (hidden,)) * (hidden * c_in) ** -0.5,
         "b_out": n(ks[3], shape) * 0.1 if serve else jnp.zeros(shape)}
    if serve:
        w = _widths(ks[4], shape)
        p["q_in"] = {"f": w["f_in"], "i": w["i_in"]}
        p["q_out"] = {"f": w["f_out"], "i": w["i_out"]}
    else:
        p["q_in"] = {"f": jnp.full(shape, 4.0), "i": jnp.full(shape, 4.0)}
        p["q_out"] = {"f": jnp.full(shape, 4.0), "i": jnp.full(shape, 3.0)}
    if bn:
        if serve:
            p["bn_scale"] = jax.random.uniform(ks[8], shape, minval=0.5,
                                               maxval=1.5)
            p["bn_bias"] = n(ks[9], shape) * 0.1
            p["bn_mean"] = n(ks[10], shape) * 0.2
            p["bn_var"] = jax.random.uniform(ks[11], shape, minval=0.5,
                                             maxval=2.0)
        else:
            p["bn_scale"] = jnp.ones(shape)
            p["bn_bias"] = jnp.zeros(shape)
            p["bn_mean"] = jnp.zeros(shape)
            p["bn_var"] = jnp.ones(shape)
    return p


def hgq_dense(key, c_in: int, c_out: int, f_w: int, i_w: int, f_a: int,
              i_a: int) -> dict:
    kw, kb = jax.random.split(key)
    # no weight or bias rounds to code 0, which the lowering would drop:
    # every seed then gets the same instructions, and so the same work
    away = lambda v, lsb: jnp.where(v < 0, -1.0, 1.0) * jnp.maximum(jnp.abs(v), lsb)
    return {"w": away(jax.random.normal(kw, (c_in, c_out)) * c_in ** -0.5, 2.0 ** -f_w),
            "b": away(jax.random.normal(kb, (c_out,)) * 0.1, 2.0 ** -(f_w + f_a)),
            "q_w": {"f": jnp.full((c_in, c_out), float(f_w)),
                    "i": jnp.full((c_in, c_out), float(i_w))},
            "q_a": {"f": jnp.full((c_in,), float(f_a)),
                    "i": jnp.full((c_in,), float(i_a))}}


def make(fn, seed: int, tag: int):
    """Run ``fn(key)`` as one jitted call on the default device."""
    return jax.jit(fn)(key_for(seed, tag))


def to_host(tree):
    return jax.tree.map(lambda a: np.asarray(jax.device_get(a)), tree)
