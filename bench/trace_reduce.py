"""Reduce a JAX profiler trace (serialized XSpace) to device metrics.

What it reads:

* device planes (``/device:TPU:<n>``): their ``XLA Ops`` line (every
  operation's interval on the device) and ``XLA Modules`` line (every
  execution of a compiled program);
* host planes: the benchmark's own ``TraceAnnotation`` spans, whose names
  start with ``bench.``; ``bench.window`` bounds the traced window.

What it gives (``Reduction``): the window's length, the device's busy
time (the union of operation intervals, averaged over devices), time per
operation name and per module execution, and the idle gaps attributed to
the innermost ``bench.*`` span open on the host at the gap's middle.

Modules are found by name. Eager launches of different kernels all
compile to a module named after the launcher's inner function
(``jit_run``), so a driver may give the order of one step's modules,
ending with one of a name of its own; the modules just before each
execution of that one take the step's labels. Order on the device is
exact, while host spans and device events can sit a millisecond apart
on the trace's clock, so labels never rest on host spans. The labels
are given only where the trace has exactly that shape: as many modules
between two executions of the last one as the order names before it,
and a Pallas kernel (a ``tpu_custom_call``) inside each labelled module.
Otherwise every module keeps its name, ``label_error`` says why, and the
kernels' own metrics find nothing to read.
"""

from __future__ import annotations

import bisect
import collections
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
NO_SPAN = "(no bench span)"
#: the custom call a Pallas kernel compiles to
KERNEL_CALL = 'custom_call_target="tpu_custom_call"'
#: "jit_decode_step(123)" -> "jit_decode_step"
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclass
class Interval:
    name: str
    start: float      # ns, on the trace's clock
    end: float


def module_name(name: str) -> str:
    return _MODULE_ID.sub("", name).strip()


def op_name(name: str) -> str:
    """"%fusion.3 = f32[...] fusion(...)" -> "fusion.3"."""
    return name.split(" = ", 1)[0].lstrip("%")


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Reduction:
    window: tuple[float, float]
    devices: int
    busy_s: float
    ops: dict[str, list[float]] = field(default_factory=dict)   # name -> [n, s]
    modules: list[Interval] = field(default_factory=list)
    spans: list[Interval] = field(default_factory=list)
    idle_by_span: dict[str, float] = field(default_factory=dict)
    label_error: str | None = None

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:10]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, v[1]] for n, v in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce(xspace: bytes, step_modules: list[str] | None = None) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_serialized_xspace(xspace),
                          step_modules)


def reduce_profile(pd, step_modules: list[str] | None = None) -> Reduction:
    spans: list[Interval] = []
    dev_ops: dict[str, list[Interval]] = {}
    modules: list[Interval] = []
    for plane in pd.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                dev_ops[plane.name] = [Interval(e.name, e.start_ns, e.end_ns)
                                       for e in line.events]
            elif is_device and line.name == MODULES_LINE:
                modules += [Interval(module_name(e.name), e.start_ns,
                                     e.end_ns) for e in line.events]
            elif not is_device:
                spans += [Interval(e.name, e.start_ns, e.end_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, found "
                         f"{len(windows)}")
    w0, w1 = windows[0].start, windows[0].end
    spans = [s for s in spans if s.end > w0 and s.start < w1]

    modules = sorted((m for m in modules if m.end > w0 and m.start < w1),
                     key=lambda m: m.start)
    names = [m.name for m in modules]
    labels, label_error = _module_labels(names, step_modules)
    starts = [m.start for m in modules]
    with_kernel: set[int] = set()
    ops: dict[str, list[float]] = collections.defaultdict(lambda: [0, 0.0])
    busy_ns = 0.0
    idle: dict[str, float] = collections.defaultdict(float)
    index = _SpanIndex(spans)
    for i, (_, evs) in enumerate(sorted(dev_ops.items())):
        evs = [e for e in evs if e.end > w0 and e.start < w1]
        for e in evs:
            j = bisect.bisect_right(starts, e.start) - 1
            inside = j >= 0 and e.start < modules[j].end
            if inside and KERNEL_CALL in e.name:
                with_kernel.add(j)
            agg = ops[(j, op_name(e.name)) if inside else op_name(e.name)]
            agg[0] += 1
            agg[1] += (min(e.end, w1) - max(e.start, w0)) * 1e-9
        busy = _union([(max(e.start, w0), min(e.end, w1)) for e in evs])
        busy_ns += sum(e - s for s, e in busy)
        if i == 0:                         # gaps of the first device
            edges = [w0] + [x for iv in busy for x in iv] + [w1]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e > s:
                    idle[index.innermost((s + e) / 2)] += (e - s) * 1e-9
    if label_error is None:
        bare = sorted({labels[j] for j in range(len(modules))
                       if labels[j] != names[j] and j not in with_kernel})
        if bare:
            label_error = f"no Pallas kernel in the modules labelled {bare}"
    if label_error is not None:
        labels = names
    for m, label in zip(modules, labels):
        m.name = label
    by_name: dict[str, list[float]] = collections.defaultdict(
        lambda: [0, 0.0])
    for k, (n_ev, sec) in ops.items():
        agg = by_name[f"{labels[k[0]]}/{k[1]}" if isinstance(k, tuple)
                      else k]
        agg[0] += n_ev
        agg[1] += sec
    n = max(len(dev_ops), 1)
    return Reduction((w0, w1), len(dev_ops), busy_ns / n * 1e-9,
                     dict(by_name), modules, spans, dict(idle), label_error)


def _module_labels(names: list[str], step_modules: list[str] | None
                   ) -> tuple[list[str], str | None]:
    """Each module's label in the step's module order, and None; or the
    names unchanged and why they could not be labelled."""
    if not step_modules:
        return names, None
    anchor, before = step_modules[-1], step_modules[:-1]
    at = [i for i, name in enumerate(names) if name == anchor]
    if not at:
        return names, f"no execution of {anchor} in the window"
    runs = [at[0]] + [b - a - 1 for a, b in zip(at, at[1:])] \
        + [len(names) - at[-1] - 1]
    # whole steps between anchors; a partial one only at either end
    odd = [r for r in runs[1:-1] if r != len(before)] \
        + [r for r in (runs[0], runs[-1]) if r > len(before)]
    if odd:
        return names, (f"{odd[0]} modules between executions of {anchor} "
                       f"where the step order names {len(before)}")
    out = list(names)
    for i in at:
        for back, label in enumerate(reversed(before), start=1):
            if i - back >= 0:
                out[i - back] = label
    return out, None


class _SpanIndex:
    """Which ``bench.*`` spans are open at a time: spans of one name never
    overlap (one host thread opens them in turn), so one bisect per name."""

    def __init__(self, spans: list[Interval]):
        by_name: dict[str, list[tuple[float, float]]] = \
            collections.defaultdict(list)
        for sp in spans:
            by_name[sp.name].append((sp.start, sp.end))
        self._names = {n: (sorted(v), [a for a, _ in sorted(v)])
                       for n, v in by_name.items()}

    def innermost(self, t: float) -> str:
        best, best_len = NO_SPAN, float("inf")
        for name, (ivs, starts) in self._names.items():
            i = bisect.bisect_right(starts, t) - 1
            if i >= 0 and t < ivs[i][1] and ivs[i][1] - ivs[i][0] < best_len:
                best, best_len = name, ivs[i][1] - ivs[i][0]
        return best


def module_seconds(tr: Reduction | None, name: str) -> tuple[int, float]:
    """(executions, device seconds) of the module named ``name``."""
    if tr is None:
        return 0, 0.0
    hits = [m for m in tr.modules if m.name == name]
    return len(hits), sum(m.end - m.start for m in hits) * 1e-9


def kernel_seconds(rec: dict, kernel: str) -> float | None:
    """Mean device seconds per execution of the module labelled
    ``kernel`` (see the module docstring)."""
    n, total = module_seconds(rec.get("trace"), kernel)
    return total / n if n else None
