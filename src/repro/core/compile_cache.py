"""Compiled-kernel cache (paper §4.5 / Fig 5).

The paper caches NVRTC-compiled kernels per (kernel, problem size); we cache
AOT-compiled XLA executables per (kernel, device, problem, dtype, config).
Timings of the miss path are split the same way Fig 5 splits them:
wisdom read / compile / launch, the launch split again into the dispatch
and the wait for the device.

That cache lives in one process. :func:`enable_persistent_cache` turns on
JAX's on-disk compilation cache as well, so a second process on the same
machine skips the compiles the first one paid for.
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable

import jax

#: Where JAX keeps its on-disk compilation cache when the environment says.
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_persistent_cache(default_dir: Path | str) -> Path:
    """Turn on JAX's persistent compilation cache; return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed. Otherwise the cache goes to ``default_dir``,
    which must be a fixed path (no pid, timestamp or temporary name), so
    that the next process looks where this one wrote.

    Example::

        enable_persistent_cache(Path(__file__).parent / ".jax_cache")
    """
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return Path(env)
    d = Path(default_dir)
    d.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(d))
    return d


@dataclass
class LaunchStats:
    """Per-launch timing record (seconds)."""
    kernel: str
    cached: bool
    wisdom_read_s: float = 0.0
    select_s: float = 0.0
    compile_s: float = 0.0     # trace+lower+compile ("NVRTC" analogue)
    dispatch_s: float = 0.0    # argument wrap + executable call, which
    #                            returns before the device finishes
    wait_s: float = 0.0        # block_until_ready on the result; 0.0
    #                            when the launch did not wait
    waited: bool = False       # waited for the device: an online tuner
    #                            or a due profiler reads the latency
    tier: str = ""
    config: dict = field(default_factory=dict)

    @property
    def launch_s(self) -> float:
        """Dispatch plus wait: the device latency seen from the host on a
        waited launch, the host's dispatch time ("cuLaunchKernel") on
        the others."""
        return self.dispatch_s + self.wait_s


class CompileCache:
    def __init__(self) -> None:
        self._cache: dict[Any, Callable] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key) -> Callable | None:
        with self._lock:
            fn = self._cache.get(key)
            if fn is not None:
                self.hits += 1
            return fn

    def put(self, key, fn: Callable) -> None:
        with self._lock:
            self._cache[key] = fn
            self.misses += 1

    def get_or_compile(self, key, compile_fn: Callable[[], Callable]
                       ) -> tuple[Callable, float, bool]:
        """Returns (callable, compile_seconds, was_cached)."""
        fn = self.get(key)
        if fn is not None:
            return fn, 0.0, True
        t0 = time.perf_counter()
        fn = compile_fn()
        dt = time.perf_counter() - t0
        self.put(key, fn)
        return fn, dt, False

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()
            self.hits = 0
            self.misses = 0

    def __len__(self) -> int:
        return len(self._cache)
