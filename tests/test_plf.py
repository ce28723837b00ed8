"""JSC-PLF (``models/plf.py``) on the normal path, at P=8 particles of F=4
features: training through ``make_lut_train_step`` against the plain
float32 reference ``kernels.ref.plf_train_ref``; the lowered program, the
fused serving engine and ``kernels.ref.plf_ref`` code for code; the
served output's invariance under a shuffle of particles; and the
parameter-free ``WindowSum`` node in training."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lower import WindowSum, lower
from repro.core.quant import quantize_to_int
from repro.kernels.ref import plf_ref, plf_train_ref
from repro.models.plf import (IN_F, IN_I, build_plf_graph, build_plf_layers,
                              init_plf_params)
from repro.optim.adam import AdamConfig
from repro.train.steps import TrainHParams, make_lut_train_step

P, F, B = 8, 4, 32
BETA = 1e-4
ADAM = AdamConfig(lr=1e-3, clip_norm=0.0)   # unclipped: m after one step is (1 - b1) g


def _events(seed: int, n: int = B) -> np.ndarray:
    """(n, P, F) input codes on the signed (IN_F, IN_I) grid."""
    from repro.data.synthetic import jsc_plf

    x, _ = jsc_plf(seed, n, P, F)
    return quantize_to_int(x, IN_F, IN_I, True, "SAT")


def _trained_like(params, key):
    """Seeded deployment-like weights: per-cell widths drawn in 2..4 (input
    f, i), 3..5 and 2..3 (output f, i), drawn output biases and batch-norm
    statistics, as after training."""
    out = []
    for k, p in zip(jax.random.split(key, len(params)), params):
        if not p:
            out.append(p)
            continue
        ks = jax.random.split(k, 8)
        shape = p["b_out"].shape
        u = lambda kk, lo, hi: jax.random.randint(kk, shape, lo, hi + 1).astype(jnp.float32)
        p = {**p, "b_out": jax.random.normal(ks[0], shape) * 0.1,
             "q_in": {"f": u(ks[1], 2, 4), "i": u(ks[2], 2, 4)},
             "q_out": {"f": u(ks[3], 3, 5), "i": u(ks[4], 2, 3)}}
        if "bn_scale" in p:
            p.update(bn_scale=jax.random.uniform(ks[5], shape, minval=0.5, maxval=1.5),
                     bn_mean=jax.random.normal(ks[6], shape) * 0.2,
                     bn_var=jax.random.uniform(ks[7], shape, minval=0.5, maxval=2.0))
        out.append(p)
    return out


@pytest.fixture(scope="module")
def served():
    from repro.serve.api import EngineSpec, build

    layers = build_plf_layers(n_features=F)
    params = _trained_like(init_plf_params(layers, jax.random.PRNGKey(3)),
                           jax.random.PRNGKey(4))
    prog = lower(build_plf_graph(layers, n_particles=P), params)
    built = build(prog, EngineSpec(n_random=256))
    return params, prog, built


# --------------------------------------------------------------------------- #
# the set sum as a node of the train list
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("shape", [(3, 5, 2), (2, 4, 3, 6)])
def test_window_sum_apply_is_a_sum_over_sites(shape):
    x = jax.random.normal(jax.random.PRNGKey(0), shape)
    node = WindowSum()
    assert node.init(jax.random.PRNGKey(1)) == {}
    y, aux = node.apply({}, x, train=True)
    np.testing.assert_array_equal(
        np.asarray(y), np.asarray(jnp.sum(x, axis=tuple(range(1, x.ndim - 1)))))
    assert float(aux.ebops) == 0.0 and aux.updates == {}
    with pytest.raises(ValueError, match="sites"):
        node.apply({}, x[:, 0] if x.ndim == 3 else x[:, 0, 0])


# --------------------------------------------------------------------------- #
# training against the plain reference
# --------------------------------------------------------------------------- #
def _one_step(fused: bool, params, x, y):
    layers = build_plf_layers(n_features=F)
    hp = TrainHParams(adam=ADAM, beta=lambda step: BETA, lut_use_fused=fused)
    step, _ = make_lut_train_step(layers, hp, jit=False)
    from repro.optim.adam import adam_init
    with jax.default_matmul_precision("highest"):
        return jax.jit(step)(params, adam_init(params),
                             {"x": jnp.asarray(x), "y": jnp.asarray(y)})


def _train_inputs():
    from repro.data.synthetic import jsc_plf

    layers = build_plf_layers(n_features=F)
    _, step_init = make_lut_train_step(layers, TrainHParams(), jit=False)
    params, _ = step_init(jax.random.PRNGKey(5))
    x_raw, y = jsc_plf(6, B, P, F)
    x = quantize_to_int(x_raw, IN_F, IN_I, True, "SAT") * 2.0 ** -IN_F
    return params, x.astype(np.float32), y


def _rel(a, b, scale):
    return float(np.max(np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64)))
                 / scale)


def test_train_step_matches_plain_reference():
    """Loss, gradient and one Adam update of ``make_lut_train_step`` on the
    PLF list against ``plf_train_ref``.

    Both compute the same float32 function in a different order (einsums
    over a (B, P, C_in, C_out) tensor against a flattened (B·P, C_in)
    reference), so values agree to float32 round-off, ~1e-7 relative.
    Limits: loss 1e-5 relative; each gradient leaf within 1e-4 of the
    largest gradient entry (the encoder's output bias, which its batch-norm
    cancels, has a true gradient of 0 and reads ~1e-5 of round-off); the
    update within 1e-3·lr wherever the reference gradient is at least
    1e-3 of the largest (Adam's first step is lr·g/(|g| + eps): there it
    is ±lr·(1 - eps/|g|), and round-off in g moves it by lr·eps·δ/g²,
    nothing), elsewhere at most lr in size (there g is round-off, and
    so is its sign); moving statistics 1e-6.  A wrong pool (a mean) or
    statistics over the batch alone miss these by orders of magnitude.
    """
    params, x, y = _train_inputs()
    new, opt, metrics = _one_step(False, params, x, y)
    (loss, aux), g = jax.value_and_grad(plf_train_ref, has_aux=True)(
        params, jnp.asarray(x), jnp.asarray(y), BETA)
    assert abs(float(metrics["loss"]) - float(loss)) <= 1e-5 * abs(float(loss))
    assert abs(float(metrics["ebops"]) - float(aux["ebops"])) <= 1e-5 * float(aux["ebops"])
    grad = jax.tree.map(lambda m: m / (1.0 - ADAM.b1), opt["m"])
    gmax = max(float(jnp.max(jnp.abs(v))) for v in jax.tree.leaves(g))
    assert gmax > 0
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(grad)[0],
                            jax.tree.leaves(g)):
        assert _rel(a, b, gmax) <= 1e-4, jax.tree_util.keystr(path)
    assert new["l1"] == {}
    for scope in ("l0", "l2"):
        for key, ref in params[scope].items():
            if key in ("bn_mean", "bn_var"):
                assert _rel(new[scope][key], aux[key], 1.0) <= 1e-6, key
                continue
            for p0, p1, gg in zip(jax.tree.leaves(ref),
                                  jax.tree.leaves(new[scope][key]),
                                  jax.tree.leaves(g[scope][key])):
                want = p0 - ADAM.lr * gg / (jnp.abs(gg) + ADAM.eps)
                big = jnp.abs(gg) >= 1e-3 * gmax
                assert _rel(jnp.where(big, p1, want), want, ADAM.lr) <= 1e-3, \
                    (scope, key)
                assert _rel(p1, p0, ADAM.lr) <= 1.0 + 1e-3, (scope, key)


def test_fused_train_flag_runs_the_particle_axis():
    """``lut_use_fused`` runs the (B, P, F) input: the encoder (batch-norm
    in training) stays on the einsum path, the head runs the fused kernel,
    and the step's loss is the einsum step's."""
    params, x, y = _train_inputs()
    _, _, m_einsum = _one_step(False, params, x, y)
    _, _, m_fused = _one_step(True, params, x, y)
    assert abs(float(m_fused["loss"]) - float(m_einsum["loss"])) <= \
        1e-5 * abs(float(m_einsum["loss"]))


def test_eval_forward_runs_fused_on_the_particle_axis():
    layers = build_plf_layers(n_features=F)
    params = _trained_like(init_plf_params(layers, jax.random.PRNGKey(8)),
                           jax.random.PRNGKey(9))
    x = jnp.asarray(_events(10) * 2.0 ** -IN_F, jnp.float32)
    enc = layers[0]
    import dataclasses
    a, _ = enc.apply(params[0], x)
    b, _ = dataclasses.replace(enc, use_fused=True).apply(params[0], x)
    assert a.shape == b.shape == (B, P, 8)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# --------------------------------------------------------------------------- #
# serving: interpreter == fused engine == plain reference
# --------------------------------------------------------------------------- #
def test_served_program_stages_and_gate(served):
    _, prog, built = served
    assert built.engine.path == "fused"
    assert built.engine.stage_kinds == ("lut", "sum", "lut")
    assert built.engine.n_inputs == P * F and built.engine.n_outputs == 5
    from repro.core.analysis import analyze_ranges, verify_program
    verify_program(prog)
    assert analyze_ranges(prog).engine_width() <= 30


def _boundary_events(prog) -> np.ndarray:
    from repro.kernels.lut_serve import input_code_bounds

    lo, hi = input_code_bounds(prog)
    rng = np.random.default_rng(12)
    edge = np.where(rng.random((64, len(lo))) < 0.5, lo, hi)
    return np.concatenate([lo[None], hi[None], np.zeros((1, len(lo)), np.int64),
                           edge]).astype(np.int64)


@pytest.mark.parametrize("inputs", ["random", "boundary"])
def test_interpreter_engine_and_reference_agree(served, inputs):
    params, prog, built = served
    codes = (_events(13).reshape(B, -1) if inputs == "random"
             else _boundary_events(prog))
    want = prog.run(codes)
    got = np.asarray(built.engine.run(codes), np.int64)
    np.testing.assert_array_equal(got, want)
    ref = plf_ref(params, jnp.asarray(codes.reshape(-1, P, F) * 2.0 ** -IN_F,
                                      jnp.float32))
    ref_codes = np.asarray(ref, np.float64) * np.exp2(np.asarray(prog.output_f))
    np.testing.assert_array_equal(ref_codes, want)


def test_pallas_engine_runs_the_same_chain(served):
    """The single-launch Pallas path packs the same three stages and agrees
    with the interpreter."""
    from repro.kernels.lut_serve import compile_program

    _, prog, _ = served
    engine = compile_program(prog, engine="pallas")
    assert engine.path == "pallas" and engine.stage_kinds == ("lut", "sum", "lut")
    codes = _events(16).reshape(B, -1)
    np.testing.assert_array_equal(np.asarray(engine.run(codes), np.int64),
                                  prog.run(codes))


def test_served_output_is_permutation_invariant(served):
    _, _, built = served
    codes = _events(14)
    perm = np.random.default_rng(15).permutation(P)
    a = np.asarray(built.engine.run(codes.reshape(B, -1)))
    b = np.asarray(built.engine.run(codes[:, perm].reshape(B, -1)))
    np.testing.assert_array_equal(a, b)


def test_chunked_train_runs_the_plf_list_with_its_scopes():
    """``chunked_train`` scans the PLF step (batches of (B, P, F) events)
    exactly as the per-step loop runs it, and the step's ops carry the
    ``l{i}_{Layer}`` scopes, the pool's included."""
    from repro.train.loop import chunked_train

    params, x, y = _train_inputs()
    layers = build_plf_layers(n_features=F)
    step, _ = make_lut_train_step(layers, TrainHParams(adam=ADAM), jit=False)
    from repro.optim.adam import adam_init
    half = B // 2
    get = lambda s: {"x": x[s % 2 * half:(s % 2 + 1) * half],
                     "y": y[s % 2 * half:(s % 2 + 1) * half]}
    res = list(chunked_train(step, params, adam_init(params), get, 0, 4,
                             chunk_steps=2, prefetch=False, donate=False))
    assert [r.k for r in res] == [2, 2]
    p, o = params, adam_init(params)
    jstep = jax.jit(step)
    for s in range(4):
        p, o, _ = jstep(p, o, jax.tree.map(jnp.asarray, get(s)))
    for a, b in zip(jax.tree.leaves(res[-1].params), jax.tree.leaves(p)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    text = jax.jit(step).lower(params, adam_init(params),
                               jax.tree.map(jnp.asarray, get(0))
                               ).as_text(debug_info=True)
    for scope in ("l0_LUTDense", "l1_WindowSum", "l2_LUTDense"):
        assert scope in text, scope


def test_table3_script_trains_the_canonical_model(capsys):
    """``benchmarks/table3_plf_tgc.run_plf`` builds from ``models/plf.py``
    (32 particles of 16 features) and trains on the normal path."""
    from benchmarks import table3_plf_tgc

    table3_plf_tgc.run_plf(steps=3, batch=16, n_train=64, n_test=32)
    out = capsys.readouterr().out
    assert "table3/plf_lut_gnn" in out and "acc=" in out
