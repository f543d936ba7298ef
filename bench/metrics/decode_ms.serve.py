"""decode_ms.serve: mean device time of one execution of the engine's
jitted decode step, found in the trace by its module name."""

from bench import trace_reduce


def read(rec):
    n, total = trace_reduce.module_seconds(rec["trace"], rec["decode_module"])
    if not n:
        return None
    return 1e3 * total / n
