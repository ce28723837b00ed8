"""Whole serve call: rows/s times integer ops per row over the cell's chips
times the int8 peak, in %."""


def read(run):
    rate = run.e2e.get("serve_rows_per_s")
    if not rate:
        return None
    return (100.0 * rate * run.work["ops_per_row"]
            / (run.chips * run.peak["int8_ops_per_s"]))
