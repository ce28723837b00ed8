"""Hand-built one-segment HGQ programs that probe the fused stage choice.

Each is a single "hgq" segment over signed inputs on the f=0 grid, built
from the same instructions the HGQ lowering emits (REQUANT, CMUL, ADD),
so the composer sees real term chains: linear ones it runs as an integer
multiply-accumulate ("mac"), and ones it must enumerate into a table.
"""

from repro.core.dais import DaisProgram, Reg, Segment


def _start(widths):
    prog = DaisProgram()
    prog.input_f = [0] * len(widths)
    prog.input_signed = [True] * len(widths)
    regs = [prog.emit("IN", (k,), Reg(0, w, True))
            for k, w in enumerate(widths)]
    return prog, regs


def _rq(prog, src, f, i):
    return prog.emit("REQUANT", (src, f, i, True, "SAT",
                                 prog.instrs[src].reg.f),
                     Reg(f, f + i + 1, True))


def _cmul(prog, src, code):
    reg = prog.instrs[src].reg
    return prog.emit("CMUL", (src, code, 0),
                     Reg(reg.f, reg.width + abs(code).bit_length() + 1, True))


def _add(prog, a, b):
    ra, rb = prog.instrs[a].reg, prog.instrs[b].reg
    f = max(ra.f, rb.f)
    width = max(ra.width + f - ra.f, rb.width + f - rb.f) + 1
    return prog.emit("ADD", (a, b), Reg(f, width, True))


def _finish(prog, in_regs, outs):
    prog.outputs = list(outs)
    prog.output_f = [prog.instrs[r].reg.f for r in outs]
    prog.segments.append(Segment(kind="hgq", layer_id=0,
                                 in_regs=tuple(in_regs),
                                 out_regs=tuple(outs)))
    return prog


def mixed_linear_prog():
    """Linear in one requant per position, with a position read bare.

    Position 0 goes through one REQUANT that both outputs share, then a
    CMUL; position 1 is read as a bare CMUL by one output and as the bare
    register by the other, on a finer grid, so the ADD aligns it.
    """
    prog, (r0, r1) = _start([8, 8])
    q0 = _rq(prog, r0, -1, 5)
    out0 = _add(prog, _cmul(prog, q0, 3), _cmul(prog, r1, -2))
    out1 = _add(prog, _cmul(prog, q0, -5), r1)
    return _finish(prog, (r0, r1), (out0, out1))


def per_cell_requant_prog():
    """Position 0 reaches the two outputs through different REQUANTs."""
    prog, (r0, r1) = _start([8, 8])
    q1 = _rq(prog, r1, -1, 5)
    out0 = _add(prog, _cmul(prog, _rq(prog, r0, -1, 5), 3),
                _cmul(prog, q1, -2))
    out1 = _add(prog, _cmul(prog, _rq(prog, r0, -2, 6), 5),
                _cmul(prog, q1, 7))
    return _finish(prog, (r0, r1), (out0, out1))


def nonlinear_prog(width: int = 8):
    """Position 0's chain is REQUANT → CMUL → REQUANT: the second requant
    rounds and clamps the product, so the term is not linear in it."""
    prog, (r0, r1) = _start([width, 8])
    p0 = _cmul(prog, _rq(prog, r0, -1, 5), 3)
    out = _add(prog, _rq(prog, p0, -2, 4), _cmul(prog, _rq(prog, r1, -1, 5), 2))
    return _finish(prog, (r0, r1), (out,))
