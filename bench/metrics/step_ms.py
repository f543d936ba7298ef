"""step_ms: the window's wall time over the time steps it completed."""


def read(rec):
    win = rec["window"]
    return 1e3 * win["wall_s"] / win["steps"]
