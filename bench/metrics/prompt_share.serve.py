"""prompt_share.serve: share of active slot-steps in the window spent
feeding a prompt token rather than producing an output token, from the
requests' prompt lengths and the steps each has been in its slot, in
percent."""


def read(rec):
    steps = rec["serve"]["per_step"]
    active = sum(p[0] for p in steps)
    if not active:
        return None
    return 100.0 * sum(p[2] for p in steps) / active
