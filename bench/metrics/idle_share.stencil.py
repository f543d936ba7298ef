"""Share of the traced window in which no operation ran on the device,
averaged over the chips used, in percent."""


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)
