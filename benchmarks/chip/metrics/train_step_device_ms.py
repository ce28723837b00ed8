"""Train step: device busy time (trace) per step, in ms."""

from readers import busy_per


def read(run):
    return busy_per(run, "steps", 1e3)
