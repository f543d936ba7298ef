"""CorrectnessOracle: does a surviving candidate compute the right thing?

The sandbox proves a config *runs*; the oracle proves it runs
*correctly*. Each check executes the built kernel (the native lowering
on a TPU, interpret mode on any other host) on concrete probe arguments and
compares against the kernel's pure-jnp reference via
:func:`repro.tuner.runner.verify_outcome` with dtype-aware rtol/atol —
the KTT-style reference-output validation the tuning literature treats
as a first-class part of any tuning run. Verdicts are cached per config
(the check is deterministic), and the check itself can run inside the
fork sandbox so a segfaulting kernel build cannot take the oracle down.
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from repro.core.builder import KernelBuilder
from repro.core.param import Config
from repro.obs import runtime as obs
from repro.tuner.runner import (VerifyOutcome, default_interpret,
                                verify_outcome)

from .evaluator import SandboxSettings, sandboxed_call
from .verdict import (STATUS_CRASH, STATUS_NUMERICS, STATUS_OK,
                      SandboxVerdict)

#: Histogram bounds for oracle max-abs-error observations (log-spaced).
ERROR_BUCKETS = (1e-8, 1e-6, 1e-4, 1e-3, 1e-2, 1e-1, 1.0, 10.0)


def _outcome_to_verdict(out: VerifyOutcome,
                        base: SandboxVerdict) -> SandboxVerdict:
    if out.ok:
        status, detail = STATUS_OK, ""
    elif out.kind == "build":
        status, detail = STATUS_CRASH, out.error
    else:                           # "structure" or "numerics"
        status, detail = STATUS_NUMERICS, out.error
    return SandboxVerdict(
        status, detail=detail, exit_cause=base.exit_cause,
        stderr=base.stderr, wall_s=base.wall_s,
        max_err=out.max_err, rtol=out.rtol, atol=out.atol)


class CorrectnessOracle:
    """Reference-output validation for one (builder, args) scenario.

    ``check(config)`` returns a :class:`SandboxVerdict`: ``ok`` (with
    ``max_err``/``rtol``/``atol`` filled in), ``numerics-mismatch``,
    ``crash`` (the kernel would not build/run), or — when constructed
    with fork ``settings`` — ``timeout``/``oom`` if the check itself had
    to be killed. Verdicts are cached by frozen config.

    Example::

        oracle = CorrectnessOracle(get_kernel("matmul"),
                                   builder.make_probe_args((256,) * 3,
                                                           "float32"))
        verdict = oracle.check({"block_m": 128, ...})
        assert verdict.ok, verdict.detail
    """

    def __init__(self, builder: KernelBuilder,
                 args: Sequence[np.ndarray],
                 interpret: bool | None = None,
                 settings: SandboxSettings | None = None) -> None:
        self.builder = builder
        self.args = [np.asarray(a) for a in args]
        self.interpret = (default_interpret() if interpret is None
                          else interpret)
        #: None = verify in-process; pass fork settings (CPU hosts only)
        #: to also contain hard crashes.
        self.settings = settings
        self.verdicts: dict[tuple, SandboxVerdict] = {}

    def _observe(self, verdict: SandboxVerdict) -> None:
        m = obs.metrics()
        if m is not None:
            m.counter("oracle.checks", kernel=self.builder.name,
                      status=verdict.status).inc()
            if verdict.max_err is not None:
                m.histogram("oracle.max_err", bounds=ERROR_BUCKETS,
                            kernel=self.builder.name
                            ).observe(verdict.max_err)
        tr = obs.tracer()
        if tr is not None and not verdict.ok:
            tr.instant("oracle." + verdict.status, cat="sandbox",
                       kernel=self.builder.name,
                       detail=verdict.detail[:200])

    def check(self, config: Config) -> SandboxVerdict:
        """The cached verdict for ``config`` (computing it on miss)."""
        key = self.builder.space.freeze(config)
        hit = self.verdicts.get(key)
        if hit is not None:
            return hit

        def run() -> VerifyOutcome:
            return verify_outcome(self.builder, config, self.args,
                                  interpret=self.interpret)

        base, outcome = sandboxed_call(
            run, self.settings if self.settings is not None
            else SandboxSettings(method="inline"))
        if base.ok:
            verdict = _outcome_to_verdict(outcome, base)
        else:
            verdict = base          # timeout / crash / oom of the check
        self.verdicts[key] = verdict
        self._observe(verdict)
        return verdict
