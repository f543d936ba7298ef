"""expert_rows_share.moe: share of the rows the held-expert matmuls
computed in the window that carry a routed (token, k) pair, in percent:
the pairs from the engine's device tally (``ServeEngine.tallies``,
``moe_routed``), the rows from the count each MoE layer records when
its step is traced (``models.moe.expert_rows``), times layers and steps.
The rest are the capacity one-hot's empty rows."""


def read(rec):
    ex = rec.get("experts")
    if not ex or not ex["rows"]:
        return None
    return 100.0 * ex["routed"] / ex["rows"]
