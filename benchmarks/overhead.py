"""Paper Fig 5: first-launch overhead breakdown (wisdom read / compile /
launch) vs cached subsequent launches — measured for real on this host with
the XLA JIT standing in for NVRTC. A launch returns once dispatched, as
``cuLaunchKernel`` does, so its rows are the host's dispatch time.

``--check`` runs the *instrumentation overhead gate* instead: the
telemetry layer (repro.obs) sits directly on the launch hot path, which
is only acceptable if the disabled path costs nothing measurable. The
gate microbenchmarks one disabled instrument site (a ``metrics()``
global read plus an ``is not None`` branch) and one disabled span site
(``with obs.span(...)``, which returns a shared no-op context) against
the pinned budget below and exits non-zero when either is blown — CI
runs this on every change. The profiler (repro.prof) sits on the same
hot path, so the gate also pins its detached site (attribute read +
branch) and the amortized cost of sampled profiling at the default
1-in-16 period.
"""

from __future__ import annotations

import time
import timeit

import numpy as np

from repro.core import WisdomKernel, get_kernel
from repro.tuner import tune_kernel

#: Pinned gate: one *disabled* instrument site must cost at most this
#: many nanoseconds (median of repeated timeit runs). The site is one
#: function call + one branch — tens of ns on any current CPU; the
#: budget leaves ~20x headroom for slow shared CI machines while still
#: catching a disabled path that grew real work (dict building, label
#: formatting, locking).
DISABLED_SITE_BUDGET_NS = 2_000.0

#: Sanity ceiling for one *enabled* counter increment (series-key build
#: + dict lookup + float add). Not a hot-path guarantee — enabled mode
#: is allowed to cost — just a guard against accidental O(n) work per
#: event.
ENABLED_SITE_BUDGET_NS = 60_000.0

#: The detached profiler site on the launch path is one attribute read
#: + ``is not None`` — it shares the disabled-obs budget.
PROF_DISABLED_SITE_BUDGET_NS = DISABLED_SITE_BUDGET_NS

#: Amortized per-launch cost of *sampled* profiling at the default
#: period (``Profiler.due`` every launch + one full workload-hook
#: profile every 16th). The hook builds a Workload dataclass and a
#: KernelProfile — microseconds of Python — so amortized over the
#: period it must stay well under typical kernel launch latencies;
#: 25µs leaves slack for slow shared CI hosts.
PROF_SAMPLED_BUDGET_NS = 25_000.0


def _site_cost_ns(stmt: str, setup: str, number: int = 200_000,
                  repeats: int = 7) -> float:
    """Median per-iteration cost of ``stmt`` in nanoseconds."""
    timer = timeit.Timer(stmt, setup=setup, timer=time.perf_counter)
    runs = sorted(timer.repeat(repeat=repeats, number=number))
    return runs[len(runs) // 2] / number * 1e9


def measure() -> dict[str, float]:
    """Per-site instrumentation costs (ns): disabled branch, disabled
    span, enabled counter inc, and the bare-loop floor for context."""
    base = ("from repro.obs import runtime as obs\n"
            "from repro.obs.metrics import MetricsRegistry\n")
    disabled = _site_cost_ns(
        "m = obs.metrics()\n"
        "if m is not None:\n"
        "    m.counter('launch.count', kernel='k').inc()",
        base + "obs.disable()")
    enabled = _site_cost_ns(
        "m = obs.metrics()\n"
        "if m is not None:\n"
        "    m.counter('launch.count', kernel='k').inc()",
        base + "obs.disable(); obs.enable(trace=False)")
    disabled_span = _site_cost_ns(
        "with obs.span('launch.select', cat='kernel'):\n"
        "    pass",
        base + "obs.disable()")
    floor = _site_cost_ns("pass", base)
    out = {"disabled_site_ns": disabled, "disabled_span_ns": disabled_span,
           "enabled_site_ns": enabled, "loop_floor_ns": floor}
    out.update(measure_prof())
    return out


def measure_prof() -> dict[str, float]:
    """Profiler launch-path costs (ns per launch): the detached site
    (``self.profiler`` read + branch, what every unprofiled process
    pays) and the amortized cost of sampled profiling at the default
    period (``due()`` every launch, a full workload-hook profile every
    16th)."""
    setup = (
        "from repro.obs import runtime as obs\n"
        "obs.disable()\n"
        "from repro.core import get_kernel\n"
        "from repro.core.device import get_device\n"
        "from repro.prof.profiler import Profiler\n"
        "class _K:\n"
        "    profiler = None\n"
        "k = _K()\n"
        "builder = get_kernel('advec_u')\n"
        "cfg = builder.default_config()\n"
        "dev = get_device('tpu-v5e')\n"
        "pr = Profiler(sample_every=16, max_profiles=64)\n")
    detached = _site_cost_ns(
        "p = k.profiler\n"
        "if p is not None and p.due('advec_u'):\n"
        "    pass",
        setup)
    sampled = _site_cost_ns(
        "if pr.due('advec_u'):\n"
        "    pr.profile_launch(builder, cfg, (32, 32, 128), 'float32',\n"
        "                      dev, 12.5, tier='exact', baseline_us=12.0)",
        setup, number=50_000)
    return {"prof_disabled_site_ns": detached,
            "prof_sampled_amortized_ns": sampled}


def check() -> int:
    """The CI gate: measure, print, and fail on a blown budget."""
    costs = measure()
    print(f"disabled instrument site: {costs['disabled_site_ns']:.1f} ns "
          f"(budget {DISABLED_SITE_BUDGET_NS:.0f} ns)")
    print(f"disabled span site:       {costs['disabled_span_ns']:.1f} ns "
          f"(budget {DISABLED_SITE_BUDGET_NS:.0f} ns)")
    print(f"enabled counter inc:      {costs['enabled_site_ns']:.1f} ns "
          f"(budget {ENABLED_SITE_BUDGET_NS:.0f} ns)")
    print(f"detached profiler site:   "
          f"{costs['prof_disabled_site_ns']:.1f} ns "
          f"(budget {PROF_DISABLED_SITE_BUDGET_NS:.0f} ns)")
    print(f"sampled profiling (amortized, 1/16): "
          f"{costs['prof_sampled_amortized_ns']:.1f} ns "
          f"(budget {PROF_SAMPLED_BUDGET_NS:.0f} ns)")
    print(f"bare loop floor:          {costs['loop_floor_ns']:.1f} ns")
    failures = []
    if costs["disabled_site_ns"] > DISABLED_SITE_BUDGET_NS:
        failures.append("disabled-site budget blown")
    if costs["disabled_span_ns"] > DISABLED_SITE_BUDGET_NS:
        failures.append("disabled-span budget blown")
    if costs["enabled_site_ns"] > ENABLED_SITE_BUDGET_NS:
        failures.append("enabled-site budget blown")
    if costs["prof_disabled_site_ns"] > PROF_DISABLED_SITE_BUDGET_NS:
        failures.append("detached-profiler-site budget blown")
    if costs["prof_sampled_amortized_ns"] > PROF_SAMPLED_BUDGET_NS:
        failures.append("sampled-profiling budget blown")
    if failures:
        print("FAIL: " + "; ".join(failures))
        return 1
    print("OK: instrumentation overhead within pinned bounds")
    return 0


def run() -> list[str]:
    import tempfile
    rows = ["overhead,kernel,phase,seconds"]
    rng = np.random.default_rng(0)
    u, v, w = (rng.standard_normal((32, 32, 128)).astype(np.float32)
               for _ in range(3))
    scal = np.array([[1.0, 1.0, 1.0, 0]], np.float32)
    with tempfile.TemporaryDirectory() as d:
        tune_kernel(get_kernel("advec_u"), (32, 32, 128), "float32",
                    "tpu-v5e", strategy="random", max_evals=30,
                    time_budget_s=30, wisdom_dir=d)
        k = WisdomKernel(get_kernel("advec_u"), wisdom_dir=d,
                         device_kind="tpu-v5e", backend="interpret")
        k(u, v, w, scal)          # first launch: wisdom + compile + run
        for _ in range(5):
            k(u, v, w, scal)      # cached
        first = k.stats[0]
        rows.append(f"overhead,advec_u,first_wisdom_read,"
                    f"{first.wisdom_read_s:.6f}")
        rows.append(f"overhead,advec_u,first_select,{first.select_s:.6f}")
        rows.append(f"overhead,advec_u,first_compile,{first.compile_s:.6f}")
        # launches return once dispatched (no latency consumer attached)
        rows.append(f"overhead,advec_u,first_dispatch,{first.dispatch_s:.6f}")
        cached = [s.dispatch_s for s in k.stats[1:]]
        rows.append(f"overhead,advec_u,cached_dispatch_mean,"
                    f"{np.mean(cached):.6f}")
        total_first = (first.wisdom_read_s + first.select_s
                       + first.compile_s + first.dispatch_s)
        rows.append(f"overhead,advec_u,compile_fraction_of_first,"
                    f"{first.compile_s / total_first:.3f}")
    for phase, ns in measure().items():
        rows.append(f"overhead,obs,{phase},{ns / 1e9:.9f}")
    return rows


if __name__ == "__main__":
    import sys
    if "--check" in sys.argv:
        raise SystemExit(check())
    for r in run():
        print(r)
