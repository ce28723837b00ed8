"""Serving tier: rows per engine flush over the window, from the deltas of
``TierStats.n_requests`` and ``n_batches`` (program counters)."""


def read(run):
    b = run.counters.get("tier_batches")
    return run.counters["tier_requests"] / b if b else None
