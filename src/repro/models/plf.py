"""The paper's JSC-PLF particle-list jet tagger (Table III, §V-D), reusable.

A permutation-invariant set network in the JEDI-linear style, with the
paper's substitution of EinsumDense by LUT-Dense.  For an event
``x ∈ R^{P×F}`` (P particles of F features each):

    h_p = Enc(x_p)          one LUT-Dense F→width shared by every particle
    s   = Σ_p h_p           set pooling (``WindowSum`` over the particle axis)
    logits = Head(s)        LUT-Dense width→n_classes

One canonical node list, shared by training (``make_lut_train_step``: the
encoder runs time-distributed over the particle axis, its batch-norm
statistics taken over batch and particles), the lowering
(``build_plf_graph`` → ``core.lower.lower``), the serving launcher
(``launch/serve.py --model jsc-plf``) and ``benchmarks/table3_plf_tgc.py``.

The pool is a sum where JEDI-linear takes a mean: the two differ by the
constant 1/P, which the head's learned input quantizer absorbs, and the
sum lowers to the same ``WindowSum`` node as the PID model's window
accumulation.
"""

from __future__ import annotations

from typing import List

import jax

from repro.core.lower import GraphInput, ModelGraph, WindowSum
from repro.core.lut_layers import LUTDense

N_PARTICLES, N_FEATURES = 32, 16     # the generator's defaults (data.synthetic)
IN_F, IN_I = 4, 3                    # signed input grid, as JSC-HLF


def build_plf_layers(n_features: int = N_FEATURES, width: int = 8,
                     hidden: int = 8, n_classes: int = 5) -> List:
    """[encoder, set sum, head]: the list that trains and lowers."""
    return [LUTDense(n_features, width, hidden=hidden, use_batchnorm=True),
            WindowSum(),
            LUTDense(width, n_classes, hidden=hidden)]


def init_plf_params(layers, key) -> list:
    return [layer.init(k)
            for layer, k in zip(layers, jax.random.split(key, len(layers)))]


def build_plf_graph(layers, n_particles: int = N_PARTICLES,
                    in_f: int = IN_F, in_i: int = IN_I) -> ModelGraph:
    """The compilable graph over one event of ``n_particles`` particles.

    The lowered program maps ``n_particles * n_features`` signed input
    codes (particle-major) to ``n_classes`` logit codes;
    ``lower(graph, params)`` compiles it (``WindowSum`` takes ``{}`` or
    ``None``).
    """
    return ModelGraph(
        input=GraphInput(shape=(n_particles, layers[0].c_in), f=in_f, i=in_i,
                         signed=True),
        nodes=list(layers))
