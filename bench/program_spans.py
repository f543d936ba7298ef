#!/usr/bin/env python3
"""The program's own spans in a traced run: launcher and serve-loop phases.

With its profiler sink on (``repro.obs.enable(profiler=True)``) the
program enters each of its spans (``launch``, ``launch.*``, ``serve.*``;
the catalogue is in docs/observability.md) as a profiler annotation, so
they land in the XSpace host plane beside the ``bench.*`` spans, on the
clock of the device's operations. ``trace_reduce`` keeps only the
``bench.*`` spans; this module reads the program's:

* ``read(xspace) -> ProgramSpans``: the program spans that lie wholly
  inside ``bench.window``, and the window's idle time on the first
  device attributed to the innermost span, bench or program, open at
  each gap's middle;
* ``launch_host_us``, ``host_ms``, ``sample_ms``: host time per launch
  less its wait, per decode step less its wait, and ``serve.sample`` per
  decode step.

The harness neither turns the sink on nor reads these spans. To run one
cell's traced run with the sink on from the moment the profiler session
starts to the window's close:

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s> [--sink 0|1]

It prints the harness's lines, then one JSON line of the program's
readings. ``--sink 0`` runs the same traced run with the sink off, for
the cost of the sink at the same seed.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import collections  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace_reduce  # noqa: E402
from bench.trace_reduce import Interval  # noqa: E402

#: idle time under these counts as named by the program's work
NAMED_BY_PROGRAM = ("bench.update", "bench.serve.hook")


def is_program_span(name: str) -> bool:
    return name == "launch" or name.startswith(("launch.", "serve."))


@dataclass
class ProgramSpans:
    window: tuple[float, float]
    spans: list[Interval] = field(default_factory=list)   # by start
    idle_by_span: dict[str, float] = field(default_factory=dict)

    def named(self, name: str) -> list[Interval]:
        return [s for s in self.spans if s.name == name]

    def program_share(self) -> float | None:
        """Share of the idle time under a program span, ``bench.update``
        or ``bench.serve.hook``; None with no idle time."""
        total = sum(self.idle_by_span.values())
        named = sum(v for k, v in self.idle_by_span.items()
                    if is_program_span(k) or k in NAMED_BY_PROGRAM)
        return named / total if total else None


def read(xspace: bytes) -> ProgramSpans:
    from jax.profiler import ProfileData
    return read_profile(ProfileData.from_serialized_xspace(xspace))


def read_profile(pd) -> ProgramSpans:
    host: list[Interval] = []
    dev_ops: dict[str, list[tuple[float, float]]] = {}
    for plane in pd.planes:
        is_device = bool(trace_reduce.DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name == trace_reduce.OPS_LINE:
                dev_ops[plane.name] = [(e.start_ns, e.end_ns)
                                       for e in line.events]
            elif not is_device:
                host += [Interval(e.name, e.start_ns, e.end_ns)
                         for e in line.events
                         if e.name.startswith(trace_reduce.SPAN_PREFIX)
                         or is_program_span(e.name)]
    windows = [s for s in host if s.name == trace_reduce.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace_reduce.WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    w0, w1 = windows[0].start, windows[0].end
    open_ = [s for s in host if s.end > w0 and s.start < w1]
    idle: dict[str, float] = collections.defaultdict(float)
    if dev_ops:
        index = trace_reduce._SpanIndex(open_)
        busy = trace_reduce._union([(max(s, w0), min(e, w1))
                                    for s, e in dev_ops[min(dev_ops)]
                                    if e > w0 and s < w1])
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                idle[index.innermost((s + e) / 2)] += (e - s) * 1e-9
    spans = sorted((s for s in open_ if is_program_span(s.name)
                    and w0 <= s.start and s.end <= w1),
                   key=lambda s: s.start)
    return ProgramSpans((w0, w1), spans, dict(idle))


def _inner_ns(parents: list[Interval], children: list[Interval]
              ) -> list[float]:
    """For each parent, the summed length of the children inside it."""
    starts = [c.start for c in children]
    out = []
    for p in parents:
        i = bisect.bisect_left(starts, p.start)
        total = 0.0
        while i < len(children) and children[i].start < p.end:
            if children[i].end <= p.end:
                total += children[i].end - children[i].start
            i += 1
        out.append(total)
    return out


def _less_inner(ps: ProgramSpans, parent: str, child: str) -> float | None:
    """Mean ns of ``parent`` spans less their ``child`` spans."""
    parents = ps.named(parent)
    if not parents:
        return None
    inner = _inner_ns(parents, ps.named(child))
    return statistics.fmean(p.end - p.start - c
                            for p, c in zip(parents, inner))


def launch_host_us(ps: ProgramSpans) -> float | None:
    """Mean host time per eager launch: ``launch`` less ``launch.wait``."""
    ns = _less_inner(ps, "launch", "launch.wait")
    return None if ns is None else ns * 1e-3


def host_ms(ps: ProgramSpans) -> float | None:
    """Mean host time per decode step: ``serve.step`` less ``serve.wait``."""
    ns = _less_inner(ps, "serve.step", "serve.wait")
    return None if ns is None else ns * 1e-6


def sample_ms(ps: ProgramSpans) -> float | None:
    """Mean ``serve.sample`` time per decode step."""
    steps = ps.named("serve.step")
    if not steps:
        return None
    return sum(_inner_ns(steps, ps.named("serve.sample"))) / len(steps) * 1e-6


def summary(ps: ProgramSpans) -> dict:
    by_name: dict[str, list[float]] = collections.defaultdict(list)
    for s in ps.spans:
        by_name[s.name].append(s.end - s.start)
    gaps = sorted(ps.idle_by_span.items(), key=lambda kv: -kv[1])[:12]
    return {"launch_host_us": launch_host_us(ps), "host_ms": host_ms(ps),
            "sample_ms": sample_ms(ps), "program_share": ps.program_share(),
            "idle_s": sum(ps.idle_by_span.values()),
            "spans": {k: {"n": len(v), "mean_us": statistics.fmean(v) * 1e-3}
                      for k, v in sorted(by_name.items())},
            "idle_gaps": [[k, v] for k, v in gaps]}


def main(argv=None, **run_kw) -> int:
    """``run_kw`` goes to ``harness.run`` (tests drive a run on the CPU)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--sink", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)
    os.environ.pop("KERNEL_LAUNCHER_OBS", None)
    from bench import harness
    harness.prepare_environment()
    from repro import obs
    windows, base = [], harness.Window

    class SinkWindow(base):
        """The harness's window, with the program's sink on while the
        profiler session records."""

        def __init__(self, seconds: float, trace: bool):
            super().__init__(seconds, trace)
            windows.append(self)

        def tick(self) -> bool:
            started = self._session is not None
            done = super().tick()
            if args.sink and not started and self._session is not None:
                obs.enable(profiler=True)
            return done

        def close(self) -> float:
            obs.disable()
            return super().close()

    harness.Window = SinkWindow
    try:
        rc = harness.run(args.workload, args.seed, args.seconds, True,
                         T_START, **run_kw)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        harness.Window = base
    line = {"workload": args.workload, "seed": args.seed, "sink": args.sink}
    line.update(summary(read(windows[-1].xspace)))
    print(json.dumps(line), file=run_kw.get("out", sys.stdout), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
