#!/usr/bin/env python3
"""Readings that the correctness limits are set from, at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 \
        [--seconds 10] [--out FILE]

For each seed, in one process: the program's reading (what a benchmark
run compares, after a short window of the cell's own traffic) and the
control's reading on the same inputs. The control is the plain reference
put in the program's place one precision below the configuration's:

* stencil cells: the reference step computed in bfloat16 for float32
  fields, and for bfloat16 fields with the fields and results stored in
  float8 (e4m3);
* serving cells: at every position of the same prompts and served
  tokens, the token that a float8 (e4m3, one scale per tensor) forward of
  every matrix product puts first, and the gap of its float32 logit
  below the best.

A limit sits above the program's largest reading and below the control's
smallest. The benchmark's own runs never run this. Needs the chip, like a
run.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import harness  # noqa: E402

LOWER = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def stencil_control(driver) -> dict:
    """The reference step in the lower precision, on the fields the
    program's step started from, compared as a run compares."""
    import jax.numpy as jnp

    from bench.reference import stencils as ref
    gen = harness.load_module("traffic", driver.traffic["generator"])
    u, v, w, e, scal = gen.fields(driver.traffic, driver.seed,
                                  driver.config["assumed"]["inverse_spacings"])
    dtype, dt = driver.dtype, driver.dt
    lower = LOWER[dtype]
    if lower == "bfloat16":         # arithmetic in bfloat16, stored as f32
        def adv(*a):
            return ref.advec_u(*a, compute_dtype="bfloat16")

        def dif(*a):
            return ref.diff_uvw(*a, compute_dtype="bfloat16")
        lu, lv, lw, le = u, v, w, e
    else:                           # float8 storage, float32 arithmetic
        adv, dif = ref.advec_u, ref.diff_uvw
        lu, lv, lw, le = (f.astype(lower) for f in (u, v, w, e))
    ut = adv(lu, lv, lw, scal).astype(dtype)
    dut, dvt, dwt = (t.astype(dtype) for t in dif(lu, lv, lw, le, scal))
    out = {"advec_u_err": float(ref.rel_err(ut, ref.advec_u(u, v, w, scal)))}
    out["diff_uvw_err"] = max(
        float(ref.rel_err(g, r))
        for g, r in zip((dut, dvt, dwt), ref.diff_uvw(u, v, w, e, scal)))
    # the update's result stored in the lower precision
    f32 = jnp.float32
    new = tuple((x.astype(f32) + dt * t).astype(lower).astype(dtype)
                for x, t in ((u, ut.astype(f32) + dut.astype(f32)),
                             (v, dvt.astype(f32)), (w, dwt.astype(f32))))
    want = ref.update(u, v, w, ut, dut, dvt, dwt, dt)
    out["update_err"] = max(float(ref.rel_err(g, r))
                            for g, r in zip(new, want))
    return out


def serve_control(driver) -> dict:
    from bench.reference import stablelm_ref
    gap, n = stablelm_ref.control_gap(
        driver.cfg, harness.key_from_seed(driver.seed), driver.sample,
        driver.max_seq)
    return {"logit_gap": gap, "served_tokens": n}


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
    control = (serve_control(driver) if config["driver"] == "serve_batch"
               else stencil_control(driver))
    return {"seed": seed, "program": program, "control": control,
            "attempted": rec["attempted"], "failed": rec["failed"]}


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
        row = readings(args.workload, seed, args.seconds)
        rows.append(row)
        print(json.dumps(row), flush=True)
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
