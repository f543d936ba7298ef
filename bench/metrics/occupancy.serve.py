"""occupancy.serve: share of slot-steps in the window that advanced a live
request (the engine's batcher slots, read on every step), in percent."""


def read(rec):
    steps = rec["serve"]["per_step"]
    if not steps:
        return None
    return 100.0 * sum(p[0] for p in steps) / (len(steps)
                                               * rec["serve"]["n_slots"])
