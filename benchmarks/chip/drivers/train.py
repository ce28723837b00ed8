"""Training job: ``train/loop.chunked_train`` over ``make_lut_train_step``.

Set-up builds one object, the chunked train step with its state, and
drives it from the seed through a chunk of one step, so that the state
after the first step can be read, and then one full chunk: the window's
own compiled scan, whose losses and parameters at its end are compared.
One more full chunk warms it; the window then runs the same generator on.
The reference follows the ``1 + chunk_steps`` steps that are compared.
"""

from __future__ import annotations

import contextlib
import time

import jax
import numpy as np

WARM_CHUNKS = 1


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(jax.device_get(a)), tree)


def setup(run):
    from repro.core.ebops import BetaSchedule
    from repro.optim.adam import AdamConfig, adam_init
    from repro.train.loop import chunked_train
    from repro.train.steps import TrainHParams, make_lut_train_step

    cfg, mix, model = run.cfg, run.traffic, run.model
    batch, k = mix["batch"], mix["chunk_steps"]
    n_batches = mix["dataset_rows"] // batch
    x, y = model.train_data(cfg, run.seed, n_batches * batch)
    run.mark("data made")

    def get_batch(step: int) -> dict:
        rows = slice((step % n_batches) * batch, (step % n_batches + 1) * batch)
        return {"x": x[rows], "y": y[rows]}

    beta, adam = mix["beta"], mix["adam"]
    hp = TrainHParams(adam=AdamConfig(**adam),
                      beta=BetaSchedule(beta["init"], beta["final"], beta["steps"]))
    step_fn, _ = make_lut_train_step(model.layers(cfg), hp, jit=False)
    params = model.make_weights(cfg, run.seed, serve=False)
    run.mark("weights made")
    st = {"p0": _host(params), "get_batch": get_batch, "losses": [],
          "stack": contextlib.ExitStack()}
    st["stack"].enter_context(jax.default_matmul_precision(cfg["matmul_precision"]))
    check = checked_steps(mix)
    gen = chunked_train(step_fn, params, adam_init(params), get_batch, 0,
                        check + k * 10 ** 7, chunk_steps=k, boundaries=(1,))
    st["gen"] = gen
    first = next(gen)
    st["losses"] += [float(v) for v in first.metrics["loss"]]
    st["g1"] = jax.tree.map(lambda m: m / (1.0 - adam["b1"]),
                            _host(first.opt_state["m"]))
    rest = next(gen)
    st["losses"] += [float(v) for v in rest.metrics["loss"]]
    st["p_check"] = _host(rest.params)
    run.mark("first steps read")
    for _ in range(WARM_CHUNKS):
        next(gen)
    run.mark("full chunks warm")
    from work import lut_stack_train_ops
    run.work = {"ops_per_sample": lut_stack_train_ops(
        cfg["dims"], cfg["hidden"], cfg["batchnorm_layers"])}
    return st


def window(run, st) -> None:
    gen, steps = st["gen"], 0
    t0 = time.monotonic()
    while True:
        with jax.profiler.TraceAnnotation("bench.chunk"):
            res = next(gen)
        steps += res.k
        t = time.monotonic()
        if t - t0 >= run.seconds:
            break
    samples = steps * run.traffic["batch"]
    run.e2e["train_samples_per_s"] = samples / (t - t0)
    run.counters.update(steps=steps, samples=samples, window_s=t - t0)


def _leaf_norms(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(np.linalg.norm(np.asarray(v, np.float64)))
            for p, v in flat}


def worst_gap(prog: dict, ref: dict, keep=None) -> float:
    """Largest gap between the program's and the reference's norm of a
    leaf, over the larger of the reference's norm of that leaf and of the
    median leaf."""
    keys = [k for k in ref if keep is None or k in keep]
    median = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30) for k in keys)


def compare(losses, g1, p_check, p0, ref) -> dict:
    """The numbers compared: the worst relative gap of the step losses, of
    the first step's gradient norms (by leaf) and of the norms of the
    parameters' change over the checked steps (by leaf, leaving out leaves
    whose reference gradient is under a thousandth of the median leaf's:
    those move by round-off, or, for the batch-norm statistics, not by
    their gradient)."""
    ref_losses, ref_g1, ref_p = ref
    g_ref = _leaf_norms(ref_g1)
    median_g = float(np.median(list(g_ref.values())))
    moved = {k for k, v in g_ref.items() if v >= 1e-3 * median_g}
    delta = lambda p: jax.tree.map(lambda a, b: np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64), p, p0)
    return {"loss_gap": max(abs(a - b) / abs(b) for a, b in zip(losses, ref_losses)),
            "grad_gap": worst_gap(_leaf_norms(g1), g_ref),
            "update_gap": worst_gap(_leaf_norms(delta(p_check)),
                                    _leaf_norms(delta(ref_p)), moved)}


def checked_steps(mix: dict) -> int:
    """The steps compared: one alone, then one full chunk."""
    return 1 + mix["chunk_steps"]


def check_batches(run, get_batch):
    return [(b["x"], b["y"]) for b in map(get_batch, range(checked_steps(run.traffic)))]


def check(run, st) -> None:
    st["gen"].close()
    st["stack"].close()
    mix, n = run.traffic, checked_steps(run.traffic)
    ref = run.model.train_reference(run.cfg, st["p0"], check_batches(run, st["get_batch"]),
                                    mix["beta"], mix["adam"])
    run.checks.update(compare(st["losses"][:n], st["g1"], st["p_check"], st["p0"], ref))
    run.attempted = n
    run.failed = 0
