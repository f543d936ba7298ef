#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell named in BENCHMARK.json, warms up its shapes, measures for
``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON line as the last line of standard output.
With ``--trace 0`` it reports the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a device trace. Exits
non-zero, with no result line, where JAX finds no TPU with a row in
``bench/peaks.json`` or fewer chips than the cell asks for.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be nonnegative")
    harness.prepare_environment()
    try:
        return harness.run(args.workload, args.seed, args.seconds,
                           bool(args.trace), T_START)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2


if __name__ == "__main__":
    sys.exit(main())
