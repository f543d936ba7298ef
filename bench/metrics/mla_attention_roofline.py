"""mla_attention_roofline: the latent attention kernel's share of its
roofline. The least time the peaks allow for the work one call must do
(``bench/work_mla_moe.py`` ``latent_attention``: every live key of the
active slots read once, all heads scored against it and its values
summed; the larger of the FLOP and the byte bound), averaged over the
traced steps, over the kernel's mean device time per call: the ops named
``mla_attention`` in the decode step's module, in percent."""

import re

from bench import work_mla_moe

OP = re.compile(r"^jit_decode_step/mla_attention(\.\d+)?$")


def read(rec):
    tr = rec.get("trace")
    traced = [p for p in rec["serve"]["per_step"] if p[3]]
    if tr is None or not traced:
        return None
    calls = [v for name, v in tr.ops.items() if OP.match(name)]
    n, seconds = sum(v[0] for v in calls), sum(v[1] for v in calls)
    if not n or seconds <= 0:
        return None
    least = sum(work_mla_moe.latent_attention(rec["config"], p[0], p[1])
                .least_s(rec["peaks"]) for p in traced) / len(traced)
    return 100.0 * least / (seconds / n)
