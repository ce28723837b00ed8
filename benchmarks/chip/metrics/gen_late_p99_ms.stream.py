"""Client generator: 99th percentile of how late each request was submitted
behind its due instant (host clock, ms)."""

import pacing


def read(run):
    late = run.spans.get("gen_late_ms")
    return pacing.percentile(late, 99) if late else None
