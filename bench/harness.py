"""Run one benchmark cell once and print its result line.

Everything that belongs to one configuration, traffic mix, cell or metric
is a file found by its name in ``BENCHMARK.json``:

    bench/configs/<config>.json     sizes, source, driver
    bench/traffic/<traffic>.json    parameters of one general generator
    bench/traffic/<generator>.py    the generator
    bench/drivers/<driver>.py       builds, warms up, measures, checks
    bench/workloads/<cell>.json     limits of the cell's correctness check
    bench/metrics/<metric>.py       one reader per metric

A driver module exposes ``Driver(cell, config, traffic, seed)`` with
``setup()``, ``window(win) -> record``, ``free()`` and ``check() ->
{name: number}``, the numbers compared with the cell's limits. A metric
module exposes ``read(record) -> float | None``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import math
import os
import sys
import time
from dataclasses import dataclass
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"
#: JAX's persistent compilation cache: a fixed path inside the checkout.
CACHE_DIR = ROOT / ".bench_cache" / "jax"
#: The traced run profiles the last TRACE_SECONDS of its window.
TRACE_SECONDS = 4.0
#: Environment that would steer the program away from what the live
#: device decides, or make a run tune or capture.
CLEARED_ENV = ("KERNEL_LAUNCHER_DEVICE", "REPRO_KERNEL_BACKEND",
               "KERNEL_LAUNCHER_ONLINE", "KERNEL_LAUNCHER_CAPTURE",
               "KERNEL_LAUNCHER_PROF")


class BenchError(RuntimeError):
    """The cell cannot be run here (no chip, unknown device, bad files)."""


# -- files ---------------------------------------------------------------------


def manifest() -> dict:
    return json.loads(MANIFEST.read_text())


def load_json(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"no {kind} module {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def cell_entry(man: dict, cell: str) -> dict:
    for w in man["workloads"]:
        if w["name"] == cell:
            return w
    raise BenchError(f"no workload {cell!r} in BENCHMARK.json")


def cell_metrics(man: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of ``cell`` reports: end-to-end ones untraced,
    per-layer ones traced; a metric with a ``workloads`` key only in the
    cells it lists."""
    group = man["per_layer"] if trace else man["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def key_from_seed(seed: int):
    """A JAX key from any nonnegative seed below 2**62 (more bits than a
    32-bit key holds): both 31-bit halves are folded in."""
    import jax
    key = jax.random.PRNGKey(0)
    for part in (seed >> 31, seed & 0x7FFFFFFF):
        key = jax.random.fold_in(key, part)
    return key


# -- the measured window --------------------------------------------------------


class Window:
    """Time-boxed measurement window. ``tick()`` is called once per step and
    says when the window has closed; with ``trace`` the JAX profiler
    records the last ``TRACE_SECONDS`` inside a ``bench.window`` span."""

    def __init__(self, seconds: float, trace: bool):
        self.seconds = float(seconds)
        self.trace = trace
        self.t_open = self.t_close = None
        self._session = None
        self._window_span = None
        self.xspace: bytes | None = None

    @staticmethod
    def no_span(name: str):
        return contextlib.nullcontext()

    @property
    def tracing(self) -> bool:
        return self._session is not None

    def span(self, name: str):
        if self._session is None:
            return contextlib.nullcontext()
        from jax.profiler import TraceAnnotation
        return TraceAnnotation(name)

    def open(self) -> float:
        self.t_open = time.perf_counter()
        return self.t_open

    def tick(self) -> bool:
        now = time.perf_counter()
        elapsed = now - self.t_open
        if (self.trace and self._session is None and self.xspace is None
                and elapsed >= self.seconds - TRACE_SECONDS):
            from jax._src.lib import _profiler
            from jax.profiler import TraceAnnotation
            self._session = _profiler.ProfilerSession(
                _profiler.ProfileOptions())
            self._window_span = TraceAnnotation("bench.window")
            self._window_span.__enter__()
        return elapsed >= self.seconds

    def close(self) -> float:
        """Call once the last step's work has finished on the device."""
        self.t_close = time.perf_counter()
        if self._session is not None:
            self._window_span.__exit__(None, None, None)
            self.xspace = self._session.stop()
            self._session = None
        return self.t_close - self.t_open


# -- correctness ----------------------------------------------------------------


@dataclass
class Check:
    """One number compared with its limit: passes when finite and at most
    the limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


def limits(cell: str) -> dict[str, float]:
    return {k: float(v) for k, v in load_json("workloads", cell)["limits"].items()}


# -- device ---------------------------------------------------------------------


def require_chips(chips: int):
    """The live devices, or BenchError when they are not TPUs with a row in
    the peaks table, or fewer than the cell asks for."""
    import jax

    import bench.work as work
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is {devs[0].platform!r} "
                         f"({devs[0].device_kind!r})")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs, work.peaks(devs[0].device_kind)


def memory_peak_bytes(devs) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def enable_compile_cache() -> Path:
    import jax
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return CACHE_DIR


def prepare_environment() -> None:
    """Before JAX is imported: the program under test on the path, the
    device left to decide, and the compile cache inside the checkout."""
    for var in CLEARED_ENV:
        os.environ.pop(var, None)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


# -- one run --------------------------------------------------------------------


def run(cell: str, seed: int, seconds: float, trace: bool, t_start: float,
        require_chip=require_chips, out=sys.stdout, err=sys.stderr) -> int:
    """Set up, measure, check and print one run of ``cell``. Returns the
    exit code. ``require_chip`` is replaced by tests that drive a run on
    the CPU."""
    man = manifest()
    entry = cell_entry(man, cell)
    config = load_json("configs", entry["config"])
    traffic = load_json("traffic", entry["traffic"])
    lim = limits(cell)
    devs, pk = require_chip(int(entry["chips"]))
    enable_compile_cache()

    driver = load_module("drivers", config["driver"]).Driver(
        cell, config, traffic, seed)
    driver.setup()
    win = Window(seconds, trace)
    record = driver.window(win)
    record["setup_s"] = win.t_open - t_start
    record["peaks"] = pk
    record["memory_peak_bytes"] = memory_peak_bytes(devs)
    if trace:
        if win.xspace is None:
            raise BenchError("the traced run recorded no trace")
        import bench.trace_reduce as trace_reduce
        record["trace"] = trace_reduce.reduce(win.xspace,
                                              record.get("step_modules"))
        if record["trace"].label_error:
            record.setdefault("notes", {})["trace_labels"] = \
                record["trace"].label_error
    driver.free()
    readings = driver.check()
    if set(readings) != set(lim):
        raise BenchError(f"compared {sorted(readings)} against limits of "
                         f"{sorted(lim)}")
    checks = [Check(k, float(v), lim[k]) for k, v in readings.items()]

    metrics = {}
    for m in cell_metrics(man, cell, trace):
        value = load_module("metrics", m["name"]).read(record)
        if value is None:
            if not trace:
                raise BenchError(f"end-to-end metric {m['name']} not measured")
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs),
              "memory_peak_bytes": record["memory_peak_bytes"]}
    result = {"correct": all(c.ok for c in checks) and record["failed"] == 0,
              "attempted": record["attempted"], "failed": record["failed"],
              "metrics": metrics, "device": device}
    if trace:
        tr = record["trace"]
        device["busy_s"] = tr.busy_s
        device["window_s"] = tr.window_s
        result["breakdown"] = tr.breakdown()
    result["compared"] = {c.name: {"value": c.value, "limit": c.limit}
                          for c in checks}

    print("window " + json.dumps(record.get("notes", {}), sort_keys=True),
          file=out, flush=True)
    print(json.dumps(result), file=out, flush=True)
    for c in checks:
        print(f"compared {c.name} {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=err, flush=True)
    return 0
