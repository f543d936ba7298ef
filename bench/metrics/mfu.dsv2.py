"""mfu.dsv2: the whole DeepSeek-V2 decode step's share of the chip's peaks:
for every step the trace saw, the least time its required work allows
(``bench/work_mla_moe.py`` ``decode_step``: every weight once, all held
experts' included, and the latent attention over each active slot's live
window; the larger of the FLOP and the byte bound), summed over the
traced window, over the window, in percent."""

from bench import work_mla_moe


def read(rec):
    tr = rec.get("trace")
    traced = [p for p in rec["serve"]["per_step"] if p[3]]
    if tr is None or not tr.devices or not traced or tr.window_s <= 0:
        return None
    least = sum(work_mla_moe.decode_step(rec["config"], p[0], p[1])
                .least_s(rec["peaks"]) for p in traced)
    return 100.0 * least / tr.window_s
