"""Subprocess-isolated evaluation: run a candidate, survive anything.

``sandboxed_call`` runs an arbitrary zero-argument callable in a forked
child process with a wall-clock timeout and an optional address-space
ceiling, and maps whatever happens — a clean return, an exception, a
hang, an allocation bomb, a segfault — onto a
:class:`~repro.sandbox.verdict.SandboxVerdict` instead of propagating
the failure into the caller. ``SandboxedEvaluator`` wraps any tuner
evaluator (the ``Evaluate`` callables from :mod:`repro.tuner.runner`)
with that protection, so a tuning session can walk a space full of
crashing configs and simply record them as infeasible.

The ``fork`` start method is deliberate: nothing is pickled on the way
in (closures over builders and numpy arrays just work), and the child
inherits the warm parent state instead of re-importing jax. It is for
CPU hosts only: a chip belongs to the process that opened it, so a child
forked from that process cannot use it, and ``sandboxed_call`` refuses to
fork once an accelerator backend is live. The ``inline`` method skips
process isolation (exceptions are still mapped to verdicts) — it is the
right default where the evaluator is pure Python arithmetic (cost model)
and forking per config would dominate, and the only method on a chip.
"""

from __future__ import annotations

import multiprocessing
import os
import tempfile
import time
import traceback
from dataclasses import dataclass
from typing import Any, Callable

from repro.obs import runtime as obs
from repro.tuner.costmodel import INFEASIBLE
from repro.tuner.runner import EvalResult

from .verdict import (STATUS_CRASH, STATUS_OK, STATUS_OOM, STATUS_TIMEOUT,
                      SandboxVerdict)

#: Histogram bounds (seconds) for sandbox wall-clock metrics.
SECONDS_BUCKETS = (0.05, 0.1, 0.25, 0.5, 1.0, 2.0, 5.0, 10.0, 30.0, 60.0,
                   120.0, 300.0)

#: Captured child stderr is truncated to this many characters.
STDERR_LIMIT = 4096

DEFAULT_TIMEOUT_S = 30.0


@dataclass(frozen=True)
class SandboxSettings:
    """Isolation knobs for one sandbox.

    ``timeout_s`` is the wall-clock ceiling per call (the child is
    SIGKILLed past it); ``memory_bytes`` caps the child's address space
    via ``RLIMIT_AS`` (None = no ceiling); ``method`` picks ``"fork"``
    (real child process — survives hangs, segfaults, allocation bombs)
    or ``"inline"`` (same process; exceptions still become verdicts but
    hangs/hard crashes are NOT contained — use only for evaluators that
    cannot hang, like the pure-Python cost model).

    Example::

        settings = SandboxSettings(timeout_s=5.0,
                                   memory_bytes=512 * 2**20)
    """

    timeout_s: float = DEFAULT_TIMEOUT_S
    memory_bytes: int | None = None
    method: str = "fork"

    def __post_init__(self) -> None:
        if self.method not in ("fork", "inline"):
            raise ValueError(f"unknown sandbox method {self.method!r}; "
                             f"use 'fork' or 'inline'")


#: Settings promotion gates use for oracle checks by default: in-process
#: (interpret-mode verification cannot hang, and forking a jax-warm
#: parent per check is both slow and thread-unsafe on some platforms).
INLINE = SandboxSettings(method="inline")


def _child_main(fn: Callable[[], Any], conn, stderr_fd: int,
                memory_bytes: int | None) -> None:
    os.dup2(stderr_fd, 2)
    try:
        # Re-point faulthandler at the captured stderr: a test harness in
        # the parent may have enabled it on a dup of the original fd 2,
        # which dup2 above does not touch — a segfaulting child would
        # dump its traceback to the user's terminal instead of the log.
        import faulthandler
        faulthandler.enable(2)
    except Exception:  # pragma: no cover — faulthandler is optional
        pass
    if memory_bytes is not None:
        import resource
        try:
            resource.setrlimit(resource.RLIMIT_AS,
                               (memory_bytes, memory_bytes))
        except (ValueError, OSError):  # pragma: no cover — platform quirk
            pass
    try:
        out = fn()
        conn.send(("ok", out))
    except MemoryError:
        conn.send(("oom", "MemoryError: allocation exceeded the sandbox "
                          "memory ceiling"))
    except BaseException as e:  # noqa: BLE001 — the whole point
        detail = f"{type(e).__name__}: {e}"
        traceback.print_exc()       # lands in the captured stderr file
        conn.send(("crash", detail))
    finally:
        conn.close()


def memory_ceiling(extra_bytes: int = 512 * 2**20) -> int:
    """A usable ``memory_bytes`` value: current address-space size plus
    ``extra_bytes`` headroom.

    ``RLIMIT_AS`` caps *virtual* address space, and a forked child
    inherits the parent's mappings — a jax-warm parent can hold
    gigabytes of (mostly untouched) reservations, so an absolute cap
    like "512 MB" would make every allocation in the child fail during
    sandbox bookkeeping and misreport as a crash. Anchoring the ceiling
    to the parent's current size means "the child may allocate about
    ``extra_bytes`` more than I already have" — which is the ceiling an
    allocation-bomb test actually wants.

    Example::

        settings = SandboxSettings(memory_bytes=memory_ceiling(256 * 2**20))
    """
    current = 0
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmSize:"):
                    current = int(line.split()[1]) * 1024
                    break
    except (OSError, ValueError, IndexError):  # pragma: no cover
        pass
    return current + int(extra_bytes)


def live_accelerator_backends() -> list[str]:
    """Non-CPU JAX backends this process has initialised (initialises
    none itself)."""
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return []
    return sorted(p for p in xla_bridge.backends() if p != "cpu")


def _read_stderr(path: str) -> str:
    try:
        with open(path, "r", errors="replace") as f:
            return f.read(STDERR_LIMIT)
    except OSError:  # pragma: no cover
        return ""


def sandboxed_call(fn: Callable[[], Any],
                   settings: SandboxSettings | None = None
                   ) -> tuple[SandboxVerdict, Any]:
    """Run ``fn`` under ``settings``; return ``(verdict, payload)``.

    ``payload`` is ``fn``'s return value when the verdict is ``ok`` and
    None otherwise. With ``method="fork"`` the return value crosses a
    pipe, so it must be picklable, and the call raises ``RuntimeError``
    when this process holds an accelerator backend; with
    ``method="inline"`` anything goes (and only exceptions — not hangs or
    signals — are contained).

    Example::

        verdict, result = sandboxed_call(lambda: evaluator(config),
                                         SandboxSettings(timeout_s=5))
        if verdict.status == "timeout":
            ...
    """
    settings = settings if settings is not None else SandboxSettings()
    if settings.method == "inline":
        t0 = time.perf_counter()
        try:
            out = fn()
            return (SandboxVerdict(STATUS_OK, exit_cause="inline",
                                   wall_s=time.perf_counter() - t0), out)
        except MemoryError:
            return (SandboxVerdict(
                STATUS_OOM, detail="MemoryError",
                exit_cause="exception:MemoryError",
                wall_s=time.perf_counter() - t0), None)
        except Exception as e:  # noqa: BLE001 — map, never propagate
            return (SandboxVerdict(
                STATUS_CRASH, detail=f"{type(e).__name__}: {e}",
                exit_cause=f"exception:{type(e).__name__}",
                stderr=traceback.format_exc()[-STDERR_LIMIT:],
                wall_s=time.perf_counter() - t0), None)

    live = live_accelerator_backends()
    if live:
        raise RuntimeError(
            f"sandboxed_call(method='fork') refused: this process holds "
            f"the {', '.join(live)} backend, and a forked child cannot use "
            f"the chip; use SandboxSettings(method='inline')")
    ctx = multiprocessing.get_context("fork")
    parent_conn, child_conn = ctx.Pipe(duplex=False)
    with tempfile.NamedTemporaryFile(prefix="sandbox-stderr-",
                                     suffix=".log") as errf:
        proc = ctx.Process(target=_child_main,
                           args=(fn, child_conn, errf.fileno(),
                                 settings.memory_bytes))
        t0 = time.perf_counter()
        proc.start()
        child_conn.close()
        proc.join(settings.timeout_s)
        wall_s = time.perf_counter() - t0
        if proc.is_alive():
            proc.kill()
            proc.join(10.0)
            return (SandboxVerdict(
                STATUS_TIMEOUT,
                detail=f"exceeded {settings.timeout_s:g}s wall-clock "
                       f"ceiling",
                exit_cause="killed:timeout", stderr=_read_stderr(errf.name),
                wall_s=wall_s), None)
        stderr = _read_stderr(errf.name)
        tag, payload = None, None
        if parent_conn.poll():
            try:
                tag, payload = parent_conn.recv()
            except (EOFError, OSError):  # pragma: no cover — torn pipe
                tag = None
        parent_conn.close()
        code = proc.exitcode
        cause = (f"signal:{-code}" if code is not None and code < 0
                 else f"exit:{code}")
        if tag == "ok":
            return (SandboxVerdict(STATUS_OK, exit_cause=cause,
                                   stderr=stderr, wall_s=wall_s), payload)
        if tag == "oom":
            return (SandboxVerdict(STATUS_OOM, detail=str(payload),
                                   exit_cause=cause, stderr=stderr,
                                   wall_s=wall_s), None)
        if tag == "crash":
            return (SandboxVerdict(STATUS_CRASH, detail=str(payload),
                                   exit_cause=cause, stderr=stderr,
                                   wall_s=wall_s), None)
        # Died before reporting: a signal (segfault, abort) — or the OS
        # OOM-killer, which the memory ceiling makes attributable.
        if settings.memory_bytes is not None and code == -9:
            return (SandboxVerdict(
                STATUS_OOM, detail="killed under the sandbox memory "
                                   "ceiling", exit_cause=cause,
                stderr=stderr, wall_s=wall_s), None)
        return (SandboxVerdict(
            STATUS_CRASH,
            detail=f"child died without reporting ({cause})",
            exit_cause=cause, stderr=stderr, wall_s=wall_s), None)


class SandboxedEvaluator:
    """Crash-isolation wrapper around any tuner evaluator.

    A drop-in ``Evaluate`` callable: delegates each config to the
    wrapped evaluator under :func:`sandboxed_call` and returns a normal
    :class:`~repro.tuner.runner.EvalResult`. Healthy configs pass
    through untouched; a hang, crash, OOM or raise becomes an
    *infeasible* result whose ``error`` is ``"sandbox:<status>: ..."``
    and whose ``info["sandbox"]`` carries the verdict status — which is
    exactly what dataset recording (:mod:`repro.tunebench`) persists, so
    replayed spaces remember which configs kill workers. Per-config
    verdicts are kept on :attr:`verdicts` for reporting.

    Example::

        ev = SandboxedEvaluator(WallClockEvaluator(builder, args),
                                SandboxSettings(timeout_s=10))
        r = ev(config)          # never raises, never hangs forever
        if not r.feasible and r.info.get("sandbox") == "timeout":
            ...
    """

    def __init__(self, evaluator: Callable[..., EvalResult],
                 settings: SandboxSettings | None = None,
                 record_to=None) -> None:
        self.evaluator = evaluator
        self.settings = settings if settings is not None else SandboxSettings()
        #: Optional dataset recorder (``record(config, EvalResult)``).
        self.record_to = record_to
        #: Verdicts in evaluation order: ``(config, SandboxVerdict)``.
        self.verdicts: list[tuple[dict, SandboxVerdict]] = []

    def _observe(self, verdict: SandboxVerdict) -> None:
        m = obs.metrics()
        if m is not None:
            m.counter("sandbox.verdict", status=verdict.status).inc()
            if verdict.status == STATUS_TIMEOUT:
                m.histogram("sandbox.timeout_s",
                            bounds=SECONDS_BUCKETS).observe(verdict.wall_s)
        tr = obs.tracer()
        if tr is not None and verdict.status != STATUS_OK:
            tr.instant("sandbox." + verdict.status, cat="sandbox",
                       detail=verdict.detail[:200])

    def _record(self, config, result: EvalResult) -> EvalResult:
        if self.record_to is not None:
            self.record_to.record(config, result)
        return result

    def __call__(self, config) -> EvalResult:
        def run() -> tuple:
            r = self.evaluator(config)
            # reduced, picklable payload (info can hold Workloads, which
            # must not cross the fork pipe)
            return (r.score_us, r.feasible, r.verified, r.error)
        with obs.span("sandbox.eval", cat="sandbox",
                      method=self.settings.method):
            verdict, payload = sandboxed_call(run, self.settings)
        self.verdicts.append((dict(config), verdict))
        self._observe(verdict)
        if verdict.ok:
            score_us, feasible, verified, error = payload
            return self._record(config, EvalResult(
                score_us, feasible, verified=verified, error=error,
                info={"sandbox": STATUS_OK, "wall_s": verdict.wall_s}))
        return self._record(config, EvalResult(
            INFEASIBLE, False,
            error=f"sandbox:{verdict.status}: {verdict.detail}",
            info={"sandbox": verdict.status, "wall_s": verdict.wall_s,
                  "exit_cause": verdict.exit_cause}))
