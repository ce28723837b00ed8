"""Drive a whole cell on the CPU at a tiny size: everything but the look
for a chip, with the program's path optionally broken underneath."""

import time

import jax

import harness
import work

TINY = {
    "jsc-train": {"traffic": {"batch": 256, "dataset_rows": 2560, "chunk_steps": 5}},
    "jsc-stream": {"traffic": {"rate_per_s": 200, "warm_s": 0.1}},
    "pid-bulk": {"config": {"n_samples": 200},
                 "traffic": {"rows_per_device": 32, "check_rows_per_device": 4}},
    "pid-bulk-4chip": {"config": {"n_samples": 200},
                       "traffic": {"rows_per_device": 8, "check_rows_per_device": 2}},
}


def run_cell(cell: str, seed: int = 2 ** 33 + 5, seconds: float = 1.0,
             trace: bool = False, chips: int = 0) -> dict:
    run = harness.Run(harness.load_bench(), cell, seed, seconds, trace,
                      overrides=TINY[cell])
    run.devices = jax.devices()
    run.chips = chips or min(run.chips, len(run.devices))
    peaks = work.peaks
    work.peaks = lambda kind: peaks("TPU v5 lite")
    try:
        return harness.execute(run, time.monotonic())
    finally:
        work.peaks = peaks
