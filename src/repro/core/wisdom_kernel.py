"""WisdomKernel — runtime kernel selection + runtime compilation (paper §4.5).

``WisdomKernel(builder)`` is the launchable object (paper Listing 3): calling
it with kernel arguments (a) derives the problem size from the arguments,
(b) optionally *captures* the launch, (c) selects the best known configuration
from the wisdom file via the fuzzy-match heuristic, and (d) compiles the
chosen configuration just-in-time, caching the executable for subsequent
launches of the same scenario.

Works both eagerly (concrete arrays: AOT-compiled executables, timing stats)
and under an outer ``jax.jit`` trace (model integration: selection happens at
trace time from static shapes, the built kernel is inlined).

An :class:`repro.online.OnlineTuner` may be attached (explicitly via
``attach_online`` / ``repro.online.enable_online_tuning``, or automatically
when ``KERNEL_LAUNCHER_ONLINE=1``): every eager launch then reports its
selection tier, a small epsilon fraction of launches runs a candidate
config instead of the incumbent ("trial" tier), and confident winners are
promoted into the wisdom file live. Traced launches never participate —
the outer jit owns those.

An eager launch is as asynchronous as ``cuLaunchKernel``: it returns the
executable's output without waiting for the device, as ``jax.jit``
dispatch does. A compile-cache hit only selects and dispatches; the kernel
is built (``_instantiate``) and compiled on a miss alone. The launch waits
for its result only when something reads the device latency
(``LaunchStats.launch_s``): an attached online tuner, or a profiler that
samples this launch. ``LaunchStats.waited`` says which. The host runs
ahead of the device by at most one launch of each kernel: before it
dispatches, a launch waits for the output of the kernel's previous
launch if that one did not wait.

With tracing on (``repro.obs``), each eager launch is one ``launch`` span
whose children are its phases: ``launch.select``, ``launch.build`` (the
compile-cache key and lookup, and the build on a miss), ``launch.compile``
(cache misses only), ``launch.wait`` (before the dispatch, on the previous
launch still in flight), ``launch.dispatch`` and ``launch.wait`` again
(after it, on the result, when the latency is read); the parent's time
outside them is the preparation (argument metadata, problem size,
backend, capture check) and the bookkeeping after the dispatch.
"""

from __future__ import annotations

import os
import time
from pathlib import Path
from typing import Callable

import jax
import numpy as np

from repro.obs import runtime as obs
from repro.obs.metrics import UNIT_BUCKETS

from .builder import ArgsMeta, KernelBuilder, args_meta
from .capture import capture_requested, write_capture
from .compile_cache import CompileCache, LaunchStats
from .device import current_device_kind
from .param import Config
from .scenario import format_key
from .wisdom import Wisdom


def online_requested() -> bool:
    """KERNEL_LAUNCHER_ONLINE=1 auto-attaches an online tuner per kernel."""
    return os.environ.get("KERNEL_LAUNCHER_ONLINE", "").lower() in (
        "1", "true", "on", "yes")

BACKEND_ENV = "REPRO_KERNEL_BACKEND"
_VALID_BACKENDS = ("auto", "pallas", "interpret", "reference")


def resolve_backend(backend: str | None = None) -> str:
    b = backend or os.environ.get(BACKEND_ENV, "auto")
    if b not in _VALID_BACKENDS:
        raise ValueError(f"bad backend {b!r}; want one of {_VALID_BACKENDS}")
    if b == "auto":
        # Real accelerators run the Pallas lowering (Mosaic on TPU,
        # Triton on GPU — see kernels/_lowering.py); hosts without one
        # serve the reference oracle.
        b = ("pallas" if jax.default_backend() in ("tpu", "gpu")
             else "reference")
    return b


class WisdomKernel:
    def __init__(self, builder: KernelBuilder,
                 wisdom_dir: Path | str | None = None,
                 device_kind: str | None = None,
                 backend: str | None = None) -> None:
        self.builder = builder
        self.wisdom_dir = wisdom_dir
        self._device_kind = device_kind
        self._backend = backend
        self._wisdom: Wisdom | None = None
        self._wisdom_read_s = 0.0
        self._selection_cache: dict[tuple, tuple[Config, str]] = {}
        self.compile_cache = CompileCache()
        self.stats: list[LaunchStats] = []
        #: §4.5 match tier of every launch (traced ones included), tallied
        #: so callers can read selection quality without observability
        #: enabled; ``last_tier`` is the most recent launch's tier.
        self.tier_counts: dict[str, int] = {}
        self.last_tier: str | None = None
        self.online = None
        if online_requested():
            from repro.online import OnlineTuner  # deferred: avoids cycle
            self.online = OnlineTuner(self, wisdom_dir=wisdom_dir)
        #: Sampled launch profiler (see ``repro.prof``) — None unless
        #: attached explicitly or via KERNEL_LAUNCHER_PROF; the per-launch
        #: cost of the disabled site is one attribute check.
        self.profiler = None
        self._profile_baselines: dict[tuple, float | None] = {}
        #: Output arrays of the last eager launch that did not wait,
        #: until the next launch has waited for them.
        self._in_flight = None
        if os.environ.get("KERNEL_LAUNCHER_PROF"):
            from repro.prof.profiler import process_profiler  # deferred
            self.profiler = process_profiler()

    # -- pieces ---------------------------------------------------------------

    @property
    def device_kind(self) -> str:
        return self._device_kind or current_device_kind()

    def _load_wisdom(self) -> Wisdom:
        if self._wisdom is None:
            t0 = time.perf_counter()
            self._wisdom = Wisdom.load(self.builder.name, self.wisdom_dir)
            self._wisdom_read_s = time.perf_counter() - t0
        return self._wisdom

    def invalidate(self) -> None:
        """Drop cached wisdom + selections (e.g. after re-tuning)."""
        self._wisdom = None
        self._selection_cache.clear()
        self.compile_cache.clear()

    def refresh_wisdom(self) -> None:
        """Re-read wisdom and re-run selection, keeping compiled
        executables — the hot-swap path for online promotion (the promoted
        variant is prewarmed, old variants stay valid for forced use)."""
        self._wisdom = None
        self._selection_cache.clear()

    def attach_online(self, tuner) -> None:
        """Attach an online tuning service (see ``repro.online``)."""
        self.online = tuner

    def attach_profiler(self, profiler) -> None:
        """Attach a :class:`repro.prof.Profiler`: every Nth eager launch
        gets a roofline profile (bottleneck class, achieved fraction of
        peak, drift vs the wisdom-recorded baseline)."""
        self.profiler = profiler

    def prewarm(self, meta: ArgsMeta, config: Config) -> bool:
        """Compile+cache ``config`` for the scenario described by ``meta``
        ahead of any launch. Returns True if a compilation happened."""
        backend = resolve_backend(self._backend)
        problem = self.builder.get_problem_size(*meta)
        dtype = self.builder.get_dtype(*meta)
        _, _, cached = self.compile_cache.get_or_compile(
            self._cache_key(backend, problem, dtype, config),
            lambda: self._compile(self._instantiate(config, meta, backend),
                                  meta))
        return not cached

    def select_config(self, problem: tuple[int, ...], dtype: str
                      ) -> tuple[Config, str]:
        key = (self.device_kind, problem, dtype)
        if key in self._selection_cache:
            return self._selection_cache[key]
        wisdom = self._load_wisdom()
        rec, tier = wisdom.select_record(self.device_kind, problem, dtype)
        cfg = (dict(rec.config) if rec is not None
               else self.builder.default_config())
        # Exact-tier wisdom scores are this scenario's drift baseline:
        # the latency the config was promoted at. Fuzzy/transferred
        # matches came from a different scenario, so no baseline.
        self._profile_baselines[key] = (
            float(rec.score_us) if rec is not None and tier == "exact"
            and rec.score_us > 0 else None)
        m = obs.metrics()
        if m is not None and rec is not None and rec.is_transferred():
            m.histogram("select.transfer_confidence", UNIT_BUCKETS,
                        kernel=self.builder.name).observe(
                            rec.transfer_confidence())
        self._selection_cache[key] = (cfg, tier)
        return cfg, tier

    def _observe_selection(self, problem: tuple[int, ...], dtype: str,
                           tier: str) -> None:
        """Always-on tier tally + (when enabled) per-scenario metrics."""
        self.tier_counts[tier] = self.tier_counts.get(tier, 0) + 1
        self.last_tier = tier
        m = obs.metrics()
        if m is not None:
            m.counter("select.tier", kernel=self.builder.name,
                      scenario=format_key((self.device_kind, problem,
                                           dtype)),
                      tier=tier).inc()

    # -- launch ---------------------------------------------------------------

    def __call__(self, *args, config: Config | None = None):
        if any(isinstance(a, jax.core.Tracer) for a in args):
            return self._call_traced(args, config)
        with obs.span("launch", cat="kernel") as span:
            return self._launch(args, config, span)

    def _call_traced(self, args, config: Config | None):
        """Inside an outer trace: select from the static shapes and inline
        the built kernel; the outer jit owns compilation. Online tuning
        still sees the (trace-time) selection so demand from jitted
        launch streams is tracked; tuning work for it runs via
        ``OnlineTuner.tick()``, not launch hooks."""
        meta = args_meta(*args)
        problem = self.builder.get_problem_size(*meta)
        dtype = self.builder.get_dtype(*meta)
        if config is None:
            config, tier = self.select_config(problem, dtype)
        else:
            tier = "forced"
        self._observe_selection(problem, dtype, tier)
        fn = self._instantiate(config, meta, resolve_backend(self._backend))
        if self.online is not None and tier != "forced":
            self.online.observe_traced(problem, dtype, meta, config, tier)
        return fn(*args)

    def _launch(self, args, config: Config | None, span: dict | None):
        """One eager launch, phase by phase: prepare, ``launch.select``,
        ``launch.build`` (the compile-cache lookup; the build on a miss
        only), ``launch.compile`` (a miss only), ``launch.wait`` on the
        kernel's previous launch if it is still in flight,
        ``launch.dispatch`` (the executable returns before the device
        finishes) and ``launch.wait`` on the result where an online tuner
        or a due profiler reads the latency. ``span`` is the enclosing
        ``launch`` span's args (None with tracing off): the kernel and
        scenario as soon as they are known, the rest once the launch has
        finished."""
        meta = args_meta(*args)
        problem = self.builder.get_problem_size(*meta)
        dtype = self.builder.get_dtype(*meta)
        if span is not None:
            span.update(kernel=self.builder.name,
                        scenario=format_key((self.device_kind, problem,
                                             dtype)))
        backend = resolve_backend(self._backend)
        if capture_requested(self.builder.name):
            write_capture(self.builder.name, problem, dtype, args,
                          extra_meta={"device_kind": self.device_kind,
                                      "source": self.builder.source})

        online = self.online
        with obs.span("launch.select", cat="kernel"):
            t0 = time.perf_counter()
            if config is None:
                config, tier = self.select_config(problem, dtype)
            else:
                tier = "forced"
            if online is not None and tier != "forced":
                trial = online.before_launch(problem, dtype, meta, config,
                                             tier)
                if trial is not None:
                    config, tier = dict(trial), "trial"
            select_s = time.perf_counter() - t0
        self._observe_selection(problem, dtype, tier)

        with obs.span("launch.build", cat="kernel"):
            key = self._cache_key(backend, problem, dtype, config)
            compiled = self.compile_cache.get(key)
            fn = (self._instantiate(config, meta, backend)
                  if compiled is None else None)
        compile_s, cached = 0.0, compiled is not None
        if not cached:
            def compile_() -> Callable:
                with obs.span("launch.compile", cat="kernel"):
                    return self._compile(fn, meta)

            compiled, compile_s, _ = self.compile_cache.get_or_compile(
                key, compile_)

        # Wait for this launch only where something reads the latency.
        profiler = self.profiler
        profile = profiler is not None and profiler.due(self.builder.name)
        wait = online is not None or profile
        # Otherwise the host may run ahead of the device, by one launch
        # of this kernel: each un-waited launch holds its outputs' memory
        # until it has run.
        previous, self._in_flight = self._in_flight, None
        if previous is not None:
            with obs.span("launch.wait", cat="kernel"):
                # an output donated to a later program is gone; that
                # program runs after this kernel's launch anyway
                jax.block_until_ready([x for x in previous
                                       if not x.is_deleted()])
        with obs.span("launch.dispatch", cat="kernel"):
            t0 = time.perf_counter()
            out = compiled(*[np.asarray(a) if not hasattr(a, "dtype") else a
                             for a in args])
            t1 = time.perf_counter()
        wait_s = 0.0
        if wait:
            with obs.span("launch.wait", cat="kernel"):
                out = jax.block_until_ready(out)
                wait_s = time.perf_counter() - t1
        else:
            self._in_flight = jax.tree_util.tree_leaves(out)
        st = LaunchStats(
            kernel=self.builder.name, cached=cached,
            wisdom_read_s=0.0 if cached else self._wisdom_read_s,
            select_s=select_s, compile_s=compile_s, dispatch_s=t1 - t0,
            wait_s=wait_s, waited=wait, tier=tier, config=dict(config))
        self.stats.append(st)
        launch_s = st.launch_s
        m = obs.metrics()
        if m is not None:
            name = self.builder.name
            m.counter("launch.count", kernel=name).inc()
            m.counter("compile.cache", kernel=name,
                      outcome="hit" if cached else "miss").inc()
            m.counter("launch.wait", kernel=name,
                      outcome="waited" if wait else "async").inc()
            m.histogram("select.latency_us",
                        kernel=name).observe(select_s * 1e6)
            m.histogram("launch.latency_us",
                        kernel=name).observe(launch_s * 1e6)
            if not cached:
                m.histogram("compile.latency_us",
                            kernel=name).observe(compile_s * 1e6)
        if span is not None:
            span.update(tier=tier, cached=cached, waited=wait,
                        compile_us=round(compile_s * 1e6, 3),
                        launch_us=round(launch_s * 1e6, 3))
        if profile:
            profiler.profile_launch(
                self.builder, config, problem, dtype, self.device_kind,
                launch_s * 1e6, tier=tier,
                baseline_us=self._profile_baselines.get(
                    (self.device_kind, problem, dtype)))
        if online is not None:
            online.after_launch(problem, dtype, config, tier, launch_s)
        return out

    def _compile(self, fn: Callable, meta) -> Callable:
        """AOT-compile ``fn`` as a program named after the kernel, so a
        device trace shows module ``jit_<kernel>`` for its launches."""
        def named(*args):
            return fn(*args)
        named.__name__ = named.__qualname__ = self.builder.name
        return jax.jit(named).lower(*meta).compile()

    def _cache_key(self, backend: str, problem: tuple[int, ...], dtype: str,
                   config: Config) -> tuple:
        return (self.device_kind, backend, problem, dtype,
                self.builder.space.freeze(config))

    def _instantiate(self, config: Config, meta, backend: str) -> Callable:
        if backend == "reference":
            return self.builder.make_reference()
        interpret = backend == "interpret"
        return self.builder.make(config, meta, interpret=interpret)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"WisdomKernel({self.builder.name!r}, "
                f"device={self.device_kind!r})")
