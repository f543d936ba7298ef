"""Span tracing exporting Chrome ``trace_event`` JSON (Perfetto-viewable).

A :class:`Tracer` records *complete* events (``"ph": "X"`` — begin time +
duration, the compact form), *instant* events (``"ph": "i"``), and
*counter* events (``"ph": "C"`` — named numeric series Perfetto renders
as stacked track charts; the profiler exports roofline counters this
way), tagged with the subsystem as the category. ``to_chrome()`` emits
the standard ``{"traceEvents": [...]}`` wrapper that chrome://tracing and
https://ui.perfetto.dev open directly, so a serving incident can be read
as a timeline: selection, compile, launch, sync ticks, fleet steps.

Time is injected (``clock``) the same way the fleet's lease layer injects
it: production uses ``time.perf_counter``, tests drive a manual clock so
exported traces are byte-deterministic. Thread ids are mapped to small
dense ints in first-seen order for the same reason.

That clock is the tracer's own. With ``profiler`` set every span is
also entered as a ``jax.profiler.TraceAnnotation``: while a JAX profiler
session records, the span lands in its host plane under its bare name,
on the same clock as the device's operations, with its args as metadata.

The disabled path never reaches this module — ``repro.obs.runtime`` hands
instrument sites ``None`` (or a shared no-op span) instead of a tracer.
"""

from __future__ import annotations

import json
import threading
import time
from pathlib import Path
from typing import Callable

#: Keys every Chrome trace event must carry (the schema the validity
#: tests and ``validate_trace`` enforce).
REQUIRED_EVENT_KEYS = ("name", "cat", "ph", "ts", "pid", "tid")


class Tracer:
    """Collects span/instant events for one process.

    Example::

        tracer = Tracer()
        with tracer.span("launch", cat="kernel", kernel="matmul"):
            ...
        tracer.save("trace.json")     # open in Perfetto
    """

    def __init__(self, clock: Callable[[], float] | None = None,
                 pid: int = 1):
        self._clock = clock or time.perf_counter
        self._epoch = self._clock()
        self.pid = int(pid)
        self._annotate = None
        self.events: list[dict] = []
        self._tids: dict[int, int] = {}

    @property
    def profiler(self) -> bool:
        """Whether each span is also entered as a
        ``jax.profiler.TraceAnnotation`` (the profiler sink)."""
        return self._annotate is not None

    @profiler.setter
    def profiler(self, on: bool) -> None:
        self._annotate = None
        if on:
            from jax.profiler import TraceAnnotation
            self._annotate = TraceAnnotation

    def _now_us(self) -> float:
        return round((self._clock() - self._epoch) * 1e6, 3)

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tids.get(ident)
        if tid is None:
            tid = self._tids[ident] = len(self._tids)
        return tid

    def span(self, name: str, cat: str = "repro", **args) -> "_Span":
        """Record one complete event around the enclosed work. ``args``
        become the event's ``args`` dict (JSON-safe values only); the
        dict is what the ``with`` statement binds, so the body can add
        args that are known only at its end. A span left by an exception
        gets an ``error`` arg, the exception's type name."""
        return _Span(self, name, cat, args)

    def instant(self, name: str, cat: str = "repro", **args) -> None:
        """Record a zero-duration marker (promotions, sync failures)."""
        self.events.append({
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": self._now_us(), "pid": self.pid, "tid": self._tid(),
            "args": {k: v for k, v in sorted(args.items())},
        })

    def counter(self, name: str, cat: str = "repro", **values) -> None:
        """Record a counter sample (``"ph": "C"``): one or more named
        numeric series at the current time. Perfetto plots each counter
        name as a track; the kernel profiler exports achieved-fraction /
        arithmetic-intensity samples this way. Non-numeric values raise
        — counter tracks are charts, not metadata."""
        args = {}
        for k, v in sorted(values.items()):
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                raise ValueError(f"counter series {k!r} has non-numeric "
                                 f"value {v!r}")
            args[k] = v
        if not args:
            raise ValueError("counter event needs at least one series")
        self.events.append({
            "name": name, "cat": cat, "ph": "C",
            "ts": self._now_us(), "pid": self.pid, "tid": self._tid(),
            "args": args,
        })

    def to_chrome(self) -> dict:
        """The standard Chrome ``trace_event`` JSON object."""
        return {"traceEvents": list(self.events),
                "displayTimeUnit": "ms"}

    def save(self, path: Path | str) -> Path:
        path = Path(path)
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(self.to_chrome(), indent=2,
                                   sort_keys=True) + "\n")
        return path

    def clear(self) -> None:
        self.events.clear()

    def __len__(self) -> int:
        return len(self.events)


class _Span:
    """One span of a :class:`Tracer`: a plain class, not a generator
    context manager, so that a span costs few interpreter calls."""

    __slots__ = ("_tracer", "_name", "_cat", "_args", "_t0", "_annotation")

    def __init__(self, tracer: Tracer, name: str, cat: str, args: dict):
        self._tracer, self._name, self._cat, self._args = (tracer, name,
                                                           cat, args)
        self._annotation = None

    def __enter__(self) -> dict:
        tr = self._tracer
        if tr._annotate is not None:
            self._annotation = tr._annotate(self._name)
            self._annotation.__enter__()
        self._t0 = tr._now_us()
        return self._args

    def __exit__(self, exc_type, exc, tb) -> None:
        tr, args = self._tracer, self._args
        t1 = tr._now_us()
        if exc_type is not None:
            args["error"] = exc_type.__name__
        if self._annotation is not None:
            if args:
                self._annotation.set_metadata(**args)
            self._annotation.__exit__(None, None, None)
        tr.events.append({
            "name": self._name, "cat": self._cat, "ph": "X",
            "ts": self._t0, "dur": round(t1 - self._t0, 3),
            "pid": tr.pid, "tid": tr._tid(),
            "args": dict(sorted(args.items()))})


def load_trace(path: Path | str) -> dict:
    """Read a saved Chrome trace, refusing files that are not one."""
    with open(path) as f:
        doc = json.load(f)
    errors = validate_trace(doc)
    if errors:
        raise ValueError(f"{path} is not a valid Chrome trace: "
                         f"{errors[0]} ({len(errors)} problem(s))")
    return doc


def validate_trace(doc) -> list[str]:
    """Schema check for Chrome ``trace_event`` JSON: the wrapper shape,
    required per-event keys, numeric timestamps, non-negative span
    durations, and numeric counter ("C") series. Returns a list of
    problems (empty = valid)."""
    errors: list[str] = []
    if not isinstance(doc, dict) or not isinstance(
            doc.get("traceEvents"), list):
        return ["top level must be an object with a traceEvents list"]
    for i, ev in enumerate(doc["traceEvents"]):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object")
            continue
        for k in REQUIRED_EVENT_KEYS:
            if k not in ev:
                errors.append(f"event {i}: missing key {k!r}")
        for k in ("ts", "dur"):
            if k in ev and not isinstance(ev[k], (int, float)):
                errors.append(f"event {i}: {k} is not numeric")
        if ev.get("ph") == "X":
            if "dur" not in ev:
                errors.append(f"event {i}: complete event without dur")
            elif isinstance(ev["dur"], (int, float)) and ev["dur"] < 0:
                errors.append(f"event {i}: negative duration")
        if ev.get("ph") == "C":
            args = ev.get("args")
            if not isinstance(args, dict) or not args:
                errors.append(f"event {i}: counter event without series "
                              f"(args must be a non-empty object)")
            else:
                for k, v in args.items():
                    if isinstance(v, bool) or not isinstance(
                            v, (int, float)):
                        errors.append(f"event {i}: counter series {k!r} "
                                      f"is not numeric")
    return errors
