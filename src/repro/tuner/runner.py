"""Evaluators: config -> (score, validity) — the tuner's measurement step.

``CostModelEvaluator`` scores a config with the analytical simulated-TPU
model; ``WallClockEvaluator`` executes the built kernel on the TPU and times
it (it refuses to run without one unless interpret mode is asked for
explicitly). Both optionally *verify* the kernel's output against the
``ref.py`` oracle on replayed capture data — the paper's "output
verification" option in Kernel Tuner. Verification runs the native lowering
on a TPU backend and interpret mode elsewhere, and the oracle runs at
``highest`` matmul precision so a float32 reference is float32 on the chip.

Both evaluators also take ``record_to``: any object with a
``record(config, EvalResult)`` method — in practice a
:class:`~repro.tunebench.SpaceDataset` — receives every evaluation
(feasible or not) as it happens, turning any tuning session into a
recorded search space that can later be replayed without hardware.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Sequence

import jax
import numpy as np

from repro.core.builder import KernelBuilder, args_meta
from repro.core.device import DeviceSpec, current_device_kind, get_device
from repro.core.param import Config
from repro.prof.profile import profile_fields, profile_from_workload

from .costmodel import CostModel, INFEASIBLE

VERIFY_BYTES_LIMIT = 64 * 2**20  # skip in-loop verification beyond this


@dataclass
class EvalResult:
    """Outcome of evaluating one configuration.

    ``score_us`` is the objective value in microseconds (lower is
    better; ``inf`` when infeasible), ``feasible`` says whether the
    config can run at all (restrictions, VMEM, failed verification and
    build errors all make it False — ``error`` says which), and
    ``verified`` records output verification (None = not checked).

    Example::

        r = evaluator({"block_m": 128, "block_n": 128})
        if r.feasible:
            print(f"{r.score_us:.1f}us")
    """

    score_us: float
    feasible: bool
    verified: bool | None = None   # None = not checked
    error: str = ""
    info: dict = field(default_factory=dict)


def tolerances(dtype: str) -> tuple[float, float]:
    if dtype in ("bfloat16",):
        return 2e-2, 2e-2
    if dtype in ("float16",):
        return 1e-2, 1e-2
    return 1e-5, 1e-5


def default_interpret() -> bool:
    """Interpret mode unless the default backend is a TPU: what a check
    with no explicit ``interpret`` argument runs."""
    return jax.default_backend() != "tpu"


@dataclass
class VerifyOutcome:
    """Structured result of one reference-oracle comparison.

    ``kind`` classifies the failure: ``""`` (passed), ``"build"`` (the
    kernel could not be built or executed at all), ``"structure"``
    (output tree/shape mismatch) or ``"numerics"`` (executed fine but
    ``allclose`` failed). ``max_err`` is the largest absolute elementwise
    deviation seen across all compared outputs (also populated on
    success, so callers can report how close a passing config was);
    ``rtol``/``atol`` are the dtype-aware tolerances the comparison used.

    Example::

        out = verify_outcome(builder, config, probe_args)
        if not out.ok:
            print(out.kind, out.error, out.max_err)
    """

    ok: bool
    kind: str = ""
    error: str = ""
    max_err: float | None = None
    rtol: float | None = None
    atol: float | None = None


def verify_outcome(builder: KernelBuilder, config: Config,
                   args: Sequence[np.ndarray],
                   interpret: bool | None = None) -> VerifyOutcome:
    """Execute the built kernel on ``args``, compare with the reference
    oracle, and classify what happened (see :class:`VerifyOutcome`).
    ``interpret=None`` means :func:`default_interpret`.

    The comparison is dtype-aware (:func:`tolerances`) and scales the
    absolute tolerance by the reference magnitude, so low-precision
    kernels are judged against realistic accumulation error rather than
    float32 expectations.

    Example::

        out = verify_outcome(get_kernel("matmul"), config, [a, b])
        assert out.ok, out.error
    """
    if interpret is None:
        interpret = default_interpret()
    meta = args_meta(*args)
    dtype = builder.get_dtype(*meta)
    rtol, atol = tolerances(dtype)
    try:
        fn = builder.make(config, meta, interpret=interpret)
        got = jax.tree.map(np.asarray, fn(*args))
    except Exception as e:  # noqa: BLE001 — any build/run failure = invalid
        return VerifyOutcome(False, kind="build", rtol=rtol, atol=atol,
                             error=f"build/run failed: "
                                   f"{type(e).__name__}: {e}")
    ref_fn = builder.make_reference()
    with jax.default_matmul_precision("highest"):
        want = jax.tree.map(np.asarray, ref_fn(*args))
    got_leaves = jax.tree.leaves(got)
    want_leaves = jax.tree.leaves(want)
    if len(got_leaves) != len(want_leaves):
        return VerifyOutcome(False, kind="structure", rtol=rtol, atol=atol,
                             error="output structure mismatch")
    max_err = 0.0
    for g, w in zip(got_leaves, want_leaves):
        if g.shape != w.shape:
            return VerifyOutcome(
                False, kind="structure", rtol=rtol, atol=atol,
                error=f"shape mismatch {g.shape} vs {w.shape}")
        g64 = np.asarray(g, np.float64)
        w64 = np.asarray(w, np.float64)
        max_err = max(max_err, float(np.max(np.abs(g64 - w64)))
                      if g64.size else 0.0)
        scale = max(1.0, float(np.max(np.abs(w64))) if w64.size else 1.0)
        if not np.allclose(g64, w64, rtol=rtol, atol=atol * scale):
            return VerifyOutcome(
                False, kind="numerics", max_err=max_err,
                rtol=rtol, atol=atol,
                error=f"allclose failed, max abs err {max_err:.3e}")
    return VerifyOutcome(True, max_err=max_err, rtol=rtol, atol=atol)


def verify_against_reference(builder: KernelBuilder, config: Config,
                             args: Sequence[np.ndarray],
                             interpret: bool | None = None
                             ) -> tuple[bool, str]:
    """Execute the built kernel on ``args`` and compare with the oracle.

    Compatibility wrapper over :func:`verify_outcome` returning the
    historical ``(ok, message)`` pair.
    """
    out = verify_outcome(builder, config, args, interpret=interpret)
    return out.ok, out.error


class CostModelEvaluator:
    """Default objective on CPU hosts: analytical model + optional verify.

    Scores a config by running the kernel's ``workload`` hook through the
    deterministic simulated-TPU :class:`~repro.tuner.costmodel.CostModel`
    for ``device`` — no execution, so it is safe (and fast) on machines
    without the accelerator. With ``verify_args`` (typically a capture's
    replayed arguments) each distinct config is additionally executed
    once in interpret mode and checked against the reference oracle.

    Example::

        ev = CostModelEvaluator(get_kernel("matmul"), (256, 256, 256),
                                "float32", "tpu-v5e", verify="none")
        score = ev(builder.default_config()).score_us
    """

    def __init__(self, builder: KernelBuilder, problem: tuple[int, ...],
                 dtype: str, device: DeviceSpec | str,
                 verify_args: Sequence[np.ndarray] | None = None,
                 verify: str = "auto", record_to=None) -> None:
        self.builder = builder
        self.problem = tuple(problem)
        self.dtype = dtype
        self.device = get_device(device) if isinstance(device, str) else device
        self.model = CostModel(self.device)
        self.verify_args = verify_args
        self.verify = verify
        #: Optional dataset recorder: ``record(config, EvalResult)`` is
        #: called for every evaluation (see repro.tunebench).
        self.record_to = record_to
        self._verified_cache: dict[tuple, tuple[bool, str]] = {}

    def _should_verify(self) -> bool:
        if self.verify == "none" or self.verify_args is None:
            return False
        if self.verify == "full":
            return True
        nbytes = sum(int(np.asarray(a).nbytes) for a in self.verify_args)
        return nbytes <= VERIFY_BYTES_LIMIT

    def _record(self, config: Config, result: EvalResult) -> EvalResult:
        if self.record_to is not None:
            self.record_to.record(config, result)
        return result

    def __call__(self, config: Config) -> EvalResult:
        if not self.builder.space.is_valid(config):
            return self._record(
                config, EvalResult(INFEASIBLE, False, error="restricted"))
        w = self.builder.make_workload(config, self.problem, self.dtype)
        key = "|".join(f"{k}={config[k]}" for k in sorted(config))
        key += f"|{self.problem}|{self.dtype}"
        t = self.model.time(w, self.dtype, noise_key=key)
        if not np.isfinite(t):
            return self._record(
                config, EvalResult(INFEASIBLE, False, error="vmem overflow",
                                   info={"vmem_bytes": w.vmem_bytes}))
        verified: bool | None = None
        if self._should_verify():
            fkey = self.builder.space.freeze(config)
            if fkey not in self._verified_cache:
                self._verified_cache[fkey] = verify_against_reference(
                    self.builder, config, self.verify_args)
            ok, msg = self._verified_cache[fkey]
            verified = ok
            if not ok:
                return self._record(
                    config, EvalResult(INFEASIBLE, False, verified=False,
                                       error=msg))
        # Always-on profiling: in the tuner the workload is already in
        # hand, so joining it with the score costs one pure function
        # call — every recorded dataset entry gains roofline counters.
        p = profile_from_workload(
            w, self.device, self.dtype, t * 1e6,
            kernel=self.builder.name, problem_size=self.problem,
            config=config)
        return self._record(
            config, EvalResult(t * 1e6, True, verified=verified,
                               info={"workload": w,
                                     "profile": profile_fields(p)}))


class WallClockEvaluator:
    """Measure execution time on the TPU.

    Builds and jits the kernel for each config, runs a warmup plus
    ``repeats`` timed executions on the concrete ``args`` (typically a
    capture's replayed data, placed on the device once), and scores the
    best of the repeats — the paper's measured objective. A config the
    compiler refuses is infeasible. Without a TPU it raises instead of
    timing something else; ``interpret=True`` opts into timing the Pallas
    interpreter, which measures the host, not the chip.

    Example::

        cap = load_capture("captures/matmul-....capture.json")
        ev = WallClockEvaluator(get_kernel(cap.kernel_name), cap.args)
        result = ev(config)     # EvalResult with measured score_us
    """

    def __init__(self, builder: KernelBuilder, args: Sequence[np.ndarray],
                 interpret: bool | None = None, repeats: int = 3,
                 verify: bool = True, record_to=None) -> None:
        if interpret is None:
            if default_interpret():
                raise RuntimeError(
                    f"WallClockEvaluator times the TPU, and the default "
                    f"backend is {jax.default_backend()!r}; pass "
                    f"interpret=True to time the Pallas interpreter")
            interpret = False
        self.builder = builder
        self.args = [np.asarray(a) for a in args]
        self._device_args = None
        self.interpret = interpret
        self.repeats = repeats
        self.verify = verify
        #: Optional dataset recorder: ``record(config, EvalResult)`` is
        #: called for every evaluation (see repro.tunebench).
        self.record_to = record_to

    def _record(self, config: Config, result: EvalResult) -> EvalResult:
        if self.record_to is not None:
            self.record_to.record(config, result)
        return result

    def __call__(self, config: Config) -> EvalResult:
        if not self.builder.space.is_valid(config):
            return self._record(
                config, EvalResult(INFEASIBLE, False, error="restricted"))
        meta = args_meta(*self.args)
        if self.verify:
            ok, msg = verify_against_reference(
                self.builder, config, self.args, interpret=self.interpret)
            if not ok:
                return self._record(
                    config, EvalResult(INFEASIBLE, False, verified=False,
                                       error=msg))
        try:
            fn = self.builder.make(config, meta, interpret=self.interpret)
            compiled = jax.jit(fn).lower(*meta).compile()
            if self._device_args is None:
                self._device_args = jax.device_put(self.args)
            args = self._device_args
            jax.block_until_ready(compiled(*args))  # warmup
            times = []
            for _ in range(self.repeats):
                t0 = time.perf_counter()
                jax.block_until_ready(compiled(*args))
                times.append(time.perf_counter() - t0)
            score_us = min(times) * 1e6
            info: dict = {}
            if self.builder._workload is not None:
                problem = self.builder.get_problem_size(*meta)
                dtype = self.builder.get_dtype(*meta)
                w = self.builder.make_workload(config, problem, dtype)
                p = profile_from_workload(
                    w, get_device(current_device_kind()), dtype, score_us,
                    kernel=self.builder.name, problem_size=problem,
                    config=config)
                info["profile"] = profile_fields(p)
            return self._record(
                config, EvalResult(score_us, True,
                                   verified=True if self.verify else None,
                                   info=info))
        except Exception as e:  # noqa: BLE001
            return self._record(
                config, EvalResult(INFEASIBLE, False,
                                   error=f"{type(e).__name__}: {e}"))
