"""The observability on/off switch and process-wide default instances.

Counter sites follow one pattern::

    from repro import obs
    m = obs.metrics()
    if m is not None:
        m.counter("launch.count", kernel=name).inc()

and span sites another::

    with obs.span("serve.step", cat="serve") as args:
        ...                       # args is None when tracing is off

When observability is disabled (the default) ``metrics()``/``tracer()``
return ``None`` and ``span()`` returns one shared no-op context — the
per-event cost is one module-global read plus one branch, measured and
gated by ``benchmarks/overhead.py --check`` so instrumentation can sit
directly on the launch hot path.

Enable explicitly with :func:`enable` (returns the registry + tracer so
callers can snapshot/save them) or ambiently with
``KERNEL_LAUNCHER_OBS=1`` in the environment, which enables at import
time — the zero-code-change way to get telemetry out of an existing
deployment.
"""

from __future__ import annotations

import contextlib
import os

from .metrics import MetricsRegistry
from .trace import Tracer

OBS_ENV = "KERNEL_LAUNCHER_OBS"

_metrics: MetricsRegistry | None = None
_tracer: Tracer | None = None
#: What ``span()`` returns while tracing is off (reusable, binds None).
_NO_SPAN = contextlib.nullcontext()


def obs_requested() -> bool:
    """KERNEL_LAUNCHER_OBS=1 enables metrics + tracing at import time."""
    return os.environ.get(OBS_ENV, "").lower() in ("1", "true", "on", "yes")


def enable(registry: MetricsRegistry | None = None,
           tracer: Tracer | None = None,
           trace: bool = True, profiler: bool = False
           ) -> tuple[MetricsRegistry | None, Tracer | None]:
    """Turn observability on for this process.

    Installs (or accepts) a :class:`MetricsRegistry` and, unless
    ``trace=False``, a :class:`Tracer`, and returns both — idempotent:
    enabling twice keeps the already-installed instances so counters
    never reset mid-run.

    ``profiler=True`` turns on the tracer's profiler sink
    (``Tracer.profiler``: spans also land in a recording JAX profiler
    session) and installs the tracer alone: the metrics registry is
    left as it was, so counters stay off unless enabled before.
    """
    global _metrics, _tracer
    if _metrics is None and not profiler:
        _metrics = registry if registry is not None else MetricsRegistry()
    if (trace or profiler) and _tracer is None:
        _tracer = tracer if tracer is not None else Tracer()
    if profiler:
        _tracer.profiler = True
    return _metrics, _tracer


def disable() -> None:
    """Turn observability off (instrument sites see ``None`` again)."""
    global _metrics, _tracer
    _metrics = None
    _tracer = None


def enabled() -> bool:
    return _metrics is not None


def metrics() -> MetricsRegistry | None:
    """The process registry, or None when observability is disabled —
    THE hot-path check: one global read, one branch."""
    return _metrics


def tracer() -> Tracer | None:
    """The process tracer, or None when disabled (or metrics-only)."""
    return _tracer


def span(name: str, cat: str = "repro", **args):
    """A span of the process tracer around the enclosed work, binding
    its args dict; with tracing off, the shared no-op context, binding
    None. Sites on hot paths pass no ``args`` (so nothing is built when
    off) and add them to the bound dict when it is not None."""
    tr = _tracer
    if tr is None:
        return _NO_SPAN
    return tr.span(name, cat, **args)


if obs_requested():            # pragma: no cover — env-dependent
    enable()
