"""tpot_p95_ms: 95th percentile of the host-clock gaps between consecutive
decode steps in the window (the first measured from the window's open).
Each gap is the time between two tokens of every active stream."""

import statistics


def read(rec):
    gaps = rec["window"]["gaps_s"]
    return 1e3 * statistics.quantiles(gaps, n=20, method="inclusive")[18]
