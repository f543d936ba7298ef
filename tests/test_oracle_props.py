"""Property-based guarantees for the correctness oracle (ISSUE 7
satellite): across random probe inputs, shapes and dtypes, the oracle
(a) accepts a kernel that reproduces its reference exactly, (b) accepts
perturbations comfortably inside the dtype tolerance, and (c) rejects
perturbations just above it with a ``numerics-mismatch`` verdict. Runs
under real ``hypothesis`` when installed, else the deterministic compat
shim (``tests/_hypothesis_compat.py``)."""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.builder import KernelBuilder
from repro.sandbox import CorrectnessOracle
from repro.tuner.runner import tolerances

DTYPES = ["float32", "float16", "bfloat16"]


def _perturbed_identity(delta: float) -> KernelBuilder:
    """A kernel whose honest computation is the identity and whose built
    variant adds a constant ``delta`` everywhere — the smallest possible
    numerics fault, so the accept/reject boundary is exactly the oracle's
    elementwise tolerance."""
    b = KernelBuilder("oracle_props_identity", source="tests")
    b.tune("unit", (1,), default=1)

    @b.problem_size
    def _problem(x):
        return tuple(int(d) for d in x.shape)

    @b.build
    def _build(config, problem, meta, interpret=False):
        def run(x):
            return np.asarray(x, np.float64) + delta
        return run

    @b.reference
    def _reference(x):
        return np.asarray(x)

    return b


def _probe(data) -> np.ndarray:
    """A random probe array with |x| <= 1, so the comparison's reference
    scale is exactly 1 and the elementwise tolerance is atol + rtol*|x|."""
    shape = tuple(data.draw(
        st.lists(st.integers(1, 8), min_size=1, max_size=3)))
    seed = data.draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    return rng.uniform(-1.0, 1.0, shape)


def _check(delta: float, x: np.ndarray, dtype: str):
    oracle = CorrectnessOracle(_perturbed_identity(delta),
                               [x.astype(dtype)])
    return oracle.check({"unit": 1})


@settings(max_examples=50, deadline=None)
@given(st.data())
def test_reference_accepts_itself(data):
    x = _probe(data)
    dtype = data.draw(st.sampled_from(DTYPES))
    verdict = _check(0.0, x, dtype)
    assert verdict.ok, verdict.detail
    assert verdict.max_err == 0.0
    rtol, atol = tolerances(dtype)
    assert (verdict.rtol, verdict.atol) == (rtol, atol)


@settings(max_examples=50, deadline=None)
@given(st.data())
def test_perturbation_within_tolerance_accepted(data):
    x = _probe(data)
    dtype = data.draw(st.sampled_from(DTYPES))
    rtol, atol = tolerances(dtype)
    # |x| <= 1 means every element's allowed deviation is at least atol
    delta = atol * data.draw(st.floats(0.0, 0.5))
    verdict = _check(delta, x, dtype)
    assert verdict.ok, verdict.detail
    assert verdict.max_err <= delta + 1e-12


@settings(max_examples=50, deadline=None)
@given(st.data())
def test_perturbation_above_tolerance_rejected(data):
    x = _probe(data)
    dtype = data.draw(st.sampled_from(DTYPES))
    rtol, atol = tolerances(dtype)
    # |x| <= 1 bounds every element's allowed deviation by atol + rtol,
    # so anything safely past that must trip the oracle
    delta = (atol + rtol) * data.draw(st.floats(1.5, 100.0))
    verdict = _check(delta, x, dtype)
    assert verdict.status == "numerics-mismatch", verdict.status
    assert verdict.max_err is not None and verdict.max_err > atol
    assert "allclose" in verdict.detail


def test_tolerances_are_dtype_aware():
    """The same small error is acceptable for half precision and a
    failure for float32 — the oracle judges against the input dtype."""
    x = np.random.default_rng(0).uniform(-1.0, 1.0, (8, 8))
    delta = 2e-3          # between float32's 1e-5 and float16's 1e-2
    assert _check(delta, x, "float16").ok
    assert _check(delta, x, "bfloat16").ok
    assert _check(delta, x, "float32").status == "numerics-mismatch"
