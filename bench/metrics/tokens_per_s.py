"""tokens_per_s: tokens the requests held when the window closed, over the
window's wall time."""


def read(rec):
    win = rec["window"]
    return win["tokens"] / win["wall_s"]
