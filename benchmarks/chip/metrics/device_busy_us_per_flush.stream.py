"""Engine: device busy time (trace) per tier flush (``TierStats.n_batches``
delta), in microseconds."""

from readers import busy_per


def read(run):
    return busy_per(run, "tier_batches", 1e6)
