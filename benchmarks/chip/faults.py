"""Controls and faults planted under the timed path, to show that the
correctness comparison fails them.  Used by ``control.py`` on the chip and
by the tests on the CPU; the benchmark's own runs never load it.

Each function patches the program through a ``pytest.MonkeyPatch``-like
object (``setattr``), so the patch is undone when that object is.
"""

from __future__ import annotations

import numpy as np


def replace_engines(mp, fault) -> None:
    """Every engine built through ``serve.api.build`` runs
    ``fault(runner, built)`` in place of its runner, after the build's own
    gate has passed on the true runner."""
    import repro.serve.api as api

    build = api.build

    def faulty_build(*a, **k):
        built = build(*a, **k)
        built.engine._runner = fault(built.engine._runner, built)
        return built

    mp.setattr(api, "build", faulty_build)


def control_engine(mp, reference) -> None:
    """The serve control: the configuration's plain reference, computed in
    bfloat16 (``reference(codes) -> (lo, hi)``), in the engine's place."""
    import jax.numpy as jnp

    def fault(runner, built):
        dtype = built.engine.dtype
        return lambda x: jnp.asarray(reference(np.asarray(x, np.int64))[0], dtype)

    replace_engines(mp, fault)


def altered_answers(mp, rows: slice = slice(None)) -> None:
    """Answers altered where they are produced: +1 on ``rows`` of every
    engine output."""
    replace_engines(mp, lambda runner, built: (lambda x: runner(x).at[rows].add(1)))


def lost_shard(mp, chips: int) -> None:
    """The last device's rows of each output never gathered (zeros)."""
    replace_engines(mp, lambda runner, built:
                    (lambda x: runner(x).at[-(x.shape[0] // chips):].set(0)))


def _patch_train_step(mp, wrap) -> None:
    import repro.train.steps as steps

    make = steps.make_lut_train_step

    def faulty(*a, **k):
        step, init = make(*a, **k)
        return wrap(step), init

    mp.setattr(steps, "make_lut_train_step", faulty)


def stuck_state(mp) -> None:
    """A train step that returns its state unchanged."""
    def wrap(step):
        def stuck(params, opt, batch):
            _, _, metrics = step(params, opt, batch)
            return params, opt, metrics
        return stuck
    _patch_train_step(mp, wrap)


def half_batch(mp) -> None:
    """A train step that leaves out half of the batch and takes the mean
    over the rest."""
    def wrap(step):
        def half(params, opt, batch):
            n = batch["y"].shape[0] // 2
            return step(params, opt, {k: v[:n] for k, v in batch.items()})
        return half
    _patch_train_step(mp, wrap)
