"""advec_u_roofline: the least time the HBM bandwidth allows for the bytes
advec_u must move (``bench/work.py``, independent of the launch
configuration) over the kernel's mean device time per launch in the
trace, in percent. The byte bound alone: no vector-unit peak is
published, and a bound left out can only lower the share."""

from bench import trace_reduce


def read(rec):
    per_launch = trace_reduce.kernel_seconds(rec, "advec_u")
    if per_launch is None:
        return None
    return 100.0 * rec["work"]["advec_u"].least_s(rec["peaks"], flops=False) \
        / per_launch
