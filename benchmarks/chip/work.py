"""Work counts: the operations and bytes a step needs, from the model.

Serving counts read the lowered DAIS program (one integer operation per
REQUANT, LLUT, CMUL, ADD or SUB instruction; input and output codes at the
program's own register widths).  Training counts LUT-Dense layers from
their shapes.  Neither reads HLO or a kernel, so a change of
implementation does not change the work a step is credited with.
"""

from __future__ import annotations

import json
import os

INT_OPS = ("REQUANT", "LLUT", "CMUL", "ADD", "SUB")

# Elementwise operations per L-LUT cell per sample of the forward pass:
# the input quantizer (scale, round, wrap: 5), the cell MLP (per hidden unit
# a multiply, add, tanh, multiply and add: 5), the output bias (1), the
# output quantizer (scale, round, two clamps: 4) and the sum over inputs
# (1); batch-norm adds 4 (subtract, scale, multiply, add).  The backward
# pass is counted as twice the forward.
Q_IN_OPS, MLP_OPS_PER_HIDDEN, BIAS_OPS, Q_OUT_OPS, SUM_OPS, BN_OPS = 5, 5, 1, 4, 1, 4
CE_OPS_PER_CLASS = 4
BACKWARD_FACTOR = 2


def serve_work(prog) -> dict:
    """Per-row operations and bytes of a DAIS program."""
    ops = sum(1 for ins in prog.instrs if ins.op in INT_OPS)
    in_bits = sum(ins.reg.width for ins in prog.instrs if ins.op == "IN")
    out_bits = sum(prog.instrs[r].reg.width for r in prog.outputs)
    return {"ops_per_row": ops,
            "bytes_per_row": (in_bits + out_bits) / 8.0}


def least_time_s(work: dict, rows: int, peak: dict, chips: int) -> float:
    """The least time ``rows`` rows need on ``chips`` chips: the larger of
    operations over the int8 peak and bytes over HBM bandwidth."""
    ops = work["ops_per_row"] * rows / (chips * peak["int8_ops_per_s"])
    mem = work["bytes_per_row"] * rows / (chips * peak["hbm_bytes_per_s"])
    return max(ops, mem)


def lut_dense_train_ops(c_in: int, c_out: int, hidden: int, bn: bool) -> int:
    """Forward plus backward operations per sample of one LUT-Dense layer."""
    cell = (Q_IN_OPS + MLP_OPS_PER_HIDDEN * hidden + BIAS_OPS + Q_OUT_OPS
            + SUM_OPS + (BN_OPS if bn else 0))
    return (1 + BACKWARD_FACTOR) * cell * c_in * c_out


def lut_stack_train_ops(dims, hidden: int, bn_layers) -> int:
    """Per-sample training operations of a LUT-Dense stack and its
    softmax cross-entropy."""
    ops = sum(lut_dense_train_ops(ci, co, hidden, k in bn_layers)
              for k, (ci, co) in enumerate(zip(dims[:-1], dims[1:])))
    return ops + (1 + BACKWARD_FACTOR) * CE_OPS_PER_CLASS * dims[-1]


def peaks(device_kind: str) -> dict:
    """The peak table's entry for a device; an unknown device is an error."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json (known: "
                       f"{sorted(k for k in table if k != 'source')})")
    return table[device_kind]
