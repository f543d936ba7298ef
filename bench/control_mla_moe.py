#!/usr/bin/env python3
"""Readings that a serving cell's limits are set from, for cells whose
driver has a ``control()`` of its own (``bench/drivers/serve_mla_moe.py``).

    python3 bench/control_mla_moe.py --workload serve-prompt-heavy \
        --seeds 1,2,3 [--seconds 10] [--out FILE]

As ``bench/control.py`` does for the other cells: for each seed, in one
process, the program's reading (what a benchmark run compares, after a
window of the cell's own traffic) and the control's reading on the same
sample of finished requests: the token and the experts a float8 (e4m3)
forward of every matrix product picks at each position, read as the
program's are (``logit_gap`` and ``route_gap``); and how many of the
sample's (token, layer) expert choices differ between the program and
the reference. A limit sits above the program's largest reading and
below the control's smallest. The benchmark's own runs never run this. Needs the chip, like
a run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402


def readings(cell: str, seed: int, seconds: float) -> dict:
    man = harness.manifest()
    entry = harness.cell_entry(man, cell)
    config = harness.load_json("configs", entry["config"])
    traffic = harness.load_json("traffic", entry["traffic"])
    driver = harness.load_module("drivers", config["driver"]).Driver(
        cell, config, traffic, seed)
    driver.setup()
    rec = driver.window(harness.Window(seconds, trace=False))
    driver.free()
    program = driver.check()
    return {"seed": seed, "program": program, "control": driver.control(),
            "routing": driver.routing(), "attempted": rec["attempted"],
            "failed": rec["failed"], "notes": rec["notes"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated nonnegative seeds")
    ap.add_argument("--seconds", type=float, default=10.0,
                    help="window of each program reading")
    ap.add_argument("--out", type=Path)
    args = ap.parse_args(argv)
    harness.prepare_environment()
    try:
        entry = harness.cell_entry(harness.manifest(), args.workload)
        harness.require_chips(int(entry["chips"]))
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        rows.append(readings(args.workload, seed, args.seconds))
        print(json.dumps(rows[-1]), flush=True)
    names = rows[0]["program"].keys()
    summary = {"workload": args.workload, "seeds": len(rows),
               "program_max": {n: max(r["program"][n] for r in rows)
                               for n in names},
               "control_min": {n: min(r["control"][n] for r in rows)
                               for n in names},
               "host_s": time.perf_counter() - T_START}
    if args.out:
        args.out.parent.mkdir(parents=True, exist_ok=True)
        args.out.write_text(json.dumps({"summary": summary, "rows": rows},
                                       indent=1))
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
