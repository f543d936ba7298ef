"""mfu.stencil: the whole time step's share of the chip's peaks: the least
time the published peaks allow for one step's required work (21 field
passes; the larger of the FLOP and the byte bound), times the steps the
trace saw, over the traced window, in percent."""


def read(rec):
    tr = rec.get("trace")
    steps = rec["window"].get("traced_steps", 0)
    if tr is None or not tr.devices or steps <= 0 or tr.window_s <= 0:
        return None
    return 100.0 * steps * rec["work"]["step"].least_s(rec["peaks"]) \
        / tr.window_s
