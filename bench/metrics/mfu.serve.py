"""mfu.serve: the whole decode step's share of the chip's peaks: for every
step the trace saw, the least time its required work allows (2 FLOPs
per matmul parameter per active slot plus attention over each slot's
live window; bytes of every weight plus each active slot's live K/V, not
the whole arena; the larger of the two bounds), summed over the traced
window, in percent."""

from bench import work


def read(rec):
    tr = rec.get("trace")
    traced = [p for p in rec["serve"]["per_step"] if p[3]]
    if tr is None or not tr.devices or not traced or tr.window_s <= 0:
        return None
    least = sum(work.lm_decode_step(rec["config"], p[0], p[1])
                .least_s(rec["peaks"]) for p in traced)
    return 100.0 * least / tr.window_s
