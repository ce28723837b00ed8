"""Work counts on a tiny DAIS program and tiny LUT-Dense shapes, by hand."""

import pytest

import work


def _tiny_program():
    import jax

    from repro.core.dais import compile_sequential
    from repro.core.lut_layers import LUTDense

    layer = LUTDense(2, 1, hidden=2)
    return compile_sequential([layer], [layer.init(jax.random.PRNGKey(0))], 4, 3)


def test_serve_work_counts_instructions_and_code_bytes():
    prog = _tiny_program()
    ops = {op: sum(1 for i in prog.instrs if i.op == op) for op in
           ("IN", "REQUANT", "LLUT", "CMUL", "ADD")}
    # two cells of one output, each a REQUANT and an LLUT onto one grid,
    # summed by one ADD; two inputs on the 8-bit (f=4, i=3, signed) grid
    assert ops == {"IN": 2, "REQUANT": 2, "LLUT": 2, "CMUL": 0, "ADD": 1}
    w = work.serve_work(prog)
    assert w["ops_per_row"] == 5
    out_bits = prog.instrs[prog.outputs[0]].reg.width
    assert w["bytes_per_row"] == (2 * 8 + out_bits) / 8


def test_least_time_is_the_larger_bound():
    peak = {"int8_ops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    w = {"ops_per_row": 50, "bytes_per_row": 2.0}
    assert work.least_time_s(w, 4, peak, 1) == pytest.approx(2.0)   # ops
    w = {"ops_per_row": 5, "bytes_per_row": 20.0}
    assert work.least_time_s(w, 4, peak, 2) == pytest.approx(4.0)   # bytes


def test_lut_dense_train_ops_by_hand():
    # per cell: q_in 5 + 5*hidden + bias 1 + q_out 4 + sum 1 (+ BN 4),
    # forward and a backward of twice that
    assert work.lut_dense_train_ops(2, 3, 4, False) == 3 * (5 + 20 + 1 + 4 + 1) * 6
    assert work.lut_dense_train_ops(2, 3, 4, True) == 3 * (5 + 20 + 1 + 4 + 1 + 4) * 6
    assert work.lut_stack_train_ops([2, 3, 5], 4, [0]) == (
        work.lut_dense_train_ops(2, 3, 4, True)
        + work.lut_dense_train_ops(3, 5, 4, False) + 3 * 4 * 5)


def test_peaks_know_the_v5e_and_refuse_other_devices():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16e9
    for kind in ("cpu", "TPU v4", "source"):
        with pytest.raises(KeyError):
            work.peaks(kind)
