"""GPU as a second device family (ISSUE 10): device parsing tables, the
honest estimated-spec path for unknown hardware, backend-aware kernel
lowering (TPU-only Mosaic params must never reach a Triton or
interpreter lowering), GPU interpret-mode numerics against the reference
oracle, and the cross-backend transfer contract — predictions across the
TPU/GPU boundary are possible but confidence-penalized, and ``select``'s
transfer tier never serves one above the gate without the penalty
applied (property-tested against the real predictor)."""

import functools
import math
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.device import (BACKENDS, CAPABILITY_AXES, DEVICES, GPU_A100,
                               TPU_V5E, capability_vector, get_device,
                               parse_device_kind)
from repro.core.wisdom import TRANSFER_MIN_CONFIDENCE, Wisdom, WisdomRecord
from repro.kernels._lowering import active_backend, lowering_kwargs
from repro.transfer.model import (BACKEND_MISMATCH_PENALTY,
                                  ESTIMATED_SIMILARITY_CAP, DeviceModel)
from repro.transfer.predictor import (CONFIDENCE_BASE,
                                      CONFIDENCE_COVERAGE_WEIGHT,
                                      CONFIDENCE_FIT_WEIGHT,
                                      transfer_scenario)
from repro.tuner.runner import verify_against_reference
from repro.tunebench import SpaceDataset

DATASET_DIR = Path(__file__).parent.parent / "benchmarks" / "datasets"


# ---------------------------------------------------------------- parsing ----

#: (raw jax device_kind, platform) -> canonical kind. The v5p/v6e rows
#: are the regression surface for the old slugifier, which turned
#: "TPU v5p" into a prefix family that inherited v5e peaks, and matched
#: the bare "v5" marker before the "v5 lite" variants.
PARSE_TABLE = [
    ("TPU v4", "tpu", "tpu-v4"),
    ("TPU v5e", "tpu", "tpu-v5e"),
    ("TPU v5 lite", "tpu", "tpu-v5e"),
    ("TPU v5lite", "tpu", "tpu-v5e"),
    ("TPU v5p", "tpu", "tpu-v5p"),
    ("TPU v5", "tpu", "tpu-v5p"),
    ("TPU v6e", "tpu", "tpu-v6e"),
    ("TPU v6 lite", "tpu", "tpu-v6e"),
    ("NVIDIA A100-SXM4-40GB", "gpu", "gpu-a100"),
    ("NVIDIA A100-SXM4-80GB", "gpu", "gpu-a100"),
    ("NVIDIA RTX A4000", "gpu", "gpu-a4000"),
    # unknown parts slug to a backend-prefixed kind so get_device can at
    # least pick the right baseline for the estimated spec
    ("TPU v9x", "tpu", "tpu-v9x"),
    ("Tesla T4", "gpu", "gpu-tesla-t4"),
    ("AMD Instinct MI300X", "", "gpu-amd-instinct-mi300x"),
    ("cpu", "cpu", "cpu"),
    ("Apple M2", "", "cpu"),
]


@pytest.mark.parametrize("raw,platform,expected", PARSE_TABLE)
def test_parse_device_kind_table(raw, platform, expected):
    assert parse_device_kind(raw, platform) == expected


def test_parsed_known_kinds_resolve_to_table_specs():
    for raw, platform, expected in PARSE_TABLE:
        spec = get_device(parse_device_kind(raw, platform))
        if expected in DEVICES:
            assert not spec.estimated, (raw, expected)
        else:
            assert spec.estimated, (raw, expected)


# ---------------------------------------------------- device specs & table ---

def test_every_table_spec_declares_a_backend():
    for kind, spec in DEVICES.items():
        assert spec.backend in BACKENDS, kind
        assert not spec.estimated, kind
    assert get_device("gpu-a100").backend == "gpu"
    assert get_device("gpu-a4000").backend == "gpu"
    assert get_device("tpu-v5e").backend == "tpu"
    assert get_device("cpu").backend == "cpu"


def test_gpu_pair_mirrors_the_papers_hardware():
    a100, a4000 = get_device("gpu-a100"), get_device("gpu-a4000")
    assert a100.family == a4000.family == "gpu-ampere"
    assert a100.matmul_granule == a4000.matmul_granule == 16
    # the data-center part is ~4x the workstation part, like the paper's
    assert 3.0 < a100.flops_f32 / a4000.flops_f32 < 5.0
    assert 3.0 < a100.hbm_bw / a4000.hbm_bw < 4.0


def test_unknown_kind_is_estimated_not_silently_v5e():
    spec = get_device("gpu-h100")
    assert spec.estimated
    assert spec.backend == "gpu"
    assert spec.kind == "gpu-h100"
    # peaks are cloned from the *backend's* baseline, not from tpu-v5e
    assert capability_vector(spec)[:3] == capability_vector(GPU_A100)[:3]
    tpu_unknown = get_device("tpu-v9x")
    assert tpu_unknown.estimated
    assert tpu_unknown.backend == "tpu"
    assert capability_vector(tpu_unknown)[:3] == \
        capability_vector(TPU_V5E)[:3]


def test_unknown_live_device_raises(monkeypatch):
    from types import SimpleNamespace

    import jax

    from repro.core.device import UnknownDeviceError, current_device

    monkeypatch.delenv("KERNEL_LAUNCHER_DEVICE", raising=False)
    fake = SimpleNamespace(device_kind="TPU v9 ultra", platform="tpu")
    monkeypatch.setattr(jax, "devices", lambda *a, **k: [fake])
    with pytest.raises(UnknownDeviceError, match="tpu-v9-ultra"):
        current_device()
    # an explicit kind string keeps resolving to an estimated spec
    monkeypatch.setenv("KERNEL_LAUNCHER_DEVICE", "tpu-v9-ultra")
    assert current_device().estimated


# -------------------------------------------------- cross-backend model -----

def _raw_similarity(model: DeviceModel) -> float:
    """exp(-rms(log2 ratios)) with no penalty/floor — the pre-GPU value."""
    logs = [math.log2(r) for r in model.ratios().values()]
    return math.exp(-math.sqrt(sum(x * x for x in logs) / len(logs)))


def test_backend_penalty_enters_similarity():
    same = DeviceModel.between("tpu-v5e", "tpu-v4")
    cross = DeviceModel.between("tpu-v5e", "gpu-a100")
    assert same.backend_penalty() == 1.0
    assert cross.backend_penalty() == BACKEND_MISMATCH_PENALTY < 1.0
    # same-backend pairs are untouched (the pre-GPU value)
    assert same.similarity() == pytest.approx(_raw_similarity(same))
    # cross-backend similarity is exactly the penalized raw value, and
    # can never exceed the penalty factor itself
    assert cross.similarity() == pytest.approx(
        _raw_similarity(cross) * BACKEND_MISMATCH_PENALTY)
    assert cross.similarity() <= BACKEND_MISMATCH_PENALTY


def test_estimated_pair_floors_below_the_serving_gate():
    for target in ("gpu-h100", "tpu-v9x", "gpu-mystery"):
        m = DeviceModel.between("tpu-v5e", target)
        assert m.estimated()
        assert m.similarity() <= ESTIMATED_SIMILARITY_CAP
        # the cap is chosen so even a perfect fit + coverage cannot
        # reach the serving gate
        best_possible = math.sqrt(ESTIMATED_SIMILARITY_CAP) * (
            CONFIDENCE_BASE + CONFIDENCE_FIT_WEIGHT
            + CONFIDENCE_COVERAGE_WEIGHT)
        assert best_possible < TRANSFER_MIN_CONFIDENCE


def test_capability_axes_unchanged():
    # the transfer model's axes are a serialization surface (wisdom
    # provenance and reports reference them); growing the spec with
    # backend/estimated/granule fields must not have widened them
    assert CAPABILITY_AXES == ("flops_bf16", "flops_f32", "hbm_bw",
                               "vmem_bytes", "program_overhead")


# ------------------------------------------- predictor: cross-backend -------

@functools.lru_cache(maxsize=None)
def _matmul_result(target: str):
    ds = SpaceDataset.load(
        DATASET_DIR / "matmul--tpu-v5e--256x256x256--float32.space.json")
    return transfer_scenario(ds, target)


def test_cross_backend_transfer_is_eligible_but_penalized():
    result = _matmul_result("gpu-a100")
    comp = result.components
    assert comp["backends"] == "tpu->gpu"
    assert comp["backend_penalty"] == BACKEND_MISMATCH_PENALTY
    assert comp["estimated"] is False
    # the penalty costs sqrt(0.5) of confidence but the A100's peaks are
    # close enough to v5e's that the prediction still clears the gate
    assert result.eligible()
    assert result.confidence >= TRANSFER_MIN_CONFIDENCE
    same_backend = _matmul_result("tpu-v4")
    assert same_backend.components["backend_penalty"] == 1.0
    assert result.confidence < same_backend.confidence


def test_cross_backend_record_carries_backends_provenance():
    rec = _matmul_result("gpu-a100").record()
    assert rec.provenance["backends"] == "tpu->gpu"
    assert rec.device_kind == "gpu-a100"
    assert rec.is_transferred()
    # same-backend records keep the pre-GPU byte layout (no new key)
    assert "backends" not in _matmul_result("tpu-v4").record().provenance


def test_estimated_target_never_eligible():
    result = _matmul_result("gpu-h100")
    assert result.components["estimated"] is True
    assert result.confidence < TRANSFER_MIN_CONFIDENCE
    assert not result.eligible()


TARGETS = ("tpu-v4", "tpu-v5p", "gpu-a100", "gpu-a4000", "gpu-h100", "cpu")


@settings(max_examples=60, deadline=None)
@given(target=st.sampled_from(TARGETS),
       min_conf=st.sampled_from((None, 0.0, 0.25, 0.30, 0.33, 0.42,
                                 0.5, 0.9)),
       measured_score=st.floats(1.0, 100.0))
def test_select_never_serves_unpenalized_cross_backend(target, min_conf,
                                                       measured_score):
    """The regression property for the ISSUE 10 serving contract.

    For every target / gate combination: (a) the predictor's confidence
    is exactly the documented mix over its audited components, whose
    similarity already carries the backend penalty (and the estimated
    floor); (b) when ``select``'s transfer tier serves the record, its
    confidence clears the gate *with* the penalty applied and
    cross-backend provenance is stamped; (c) estimated targets never
    serve at the default gate.
    """
    result = _matmul_result(target)
    comp = result.components
    model = DeviceModel.between("tpu-v5e", target)

    # (a) confidence == sqrt(penalized similarity) x component mix
    sim = comp["similarity"]
    expected_sim = _raw_similarity(model) * model.backend_penalty()
    if model.estimated():
        expected_sim = min(expected_sim, ESTIMATED_SIMILARITY_CAP)
    assert sim == pytest.approx(expected_sim, abs=1e-6)
    expected_conf = math.sqrt(sim) * (
        CONFIDENCE_BASE + CONFIDENCE_FIT_WEIGHT * comp["fit_quality"]
        + CONFIDENCE_COVERAGE_WEIGHT * comp["coverage"])
    assert result.confidence == pytest.approx(min(1.0, expected_conf),
                                              abs=1e-6)

    # (b)+(c): build a wisdom store the way the serving path does —
    # a measured record for a *different* problem (the cold fallback)
    # plus the transferred record when it clears this gate.
    cross = get_device(target).backend != "tpu"
    wisdom = Wisdom("matmul", [WisdomRecord(
        device_kind=target, device_family=get_device(target).family,
        problem_size=(512, 512, 512), dtype="float32",
        config={"block_m": 128, "block_n": 128, "block_k": 256,
                "grid_order": "mnk", "dim_semantics": "parallel"},
        score_us=measured_score,
        provenance={"strategy": "test", "evaluations": 1})])
    if result.eligible(min_conf):
        wisdom.add(result.record())
    rec, tier = wisdom.select_record(target, (256, 256, 256), "float32",
                                    min_transfer_confidence=min_conf)
    threshold = (TRANSFER_MIN_CONFIDENCE if min_conf is None
                 else float(min_conf))
    if tier == "transfer":
        assert rec.is_transferred()
        assert rec.transfer_confidence() >= threshold
        assert rec.transfer_confidence() == pytest.approx(
            result.confidence, abs=1e-6)
        assert ("backends" in rec.provenance) == cross
        if cross:
            assert rec.provenance["backends"].split("->")[0] == "tpu"
            assert comp["backend_penalty"] < 1.0
    else:
        # no transferred record cleared the gate -> the measured
        # fallback (device tier) serves instead, never a low-confidence
        # transfer
        assert rec is not None and not rec.is_transferred()
    if get_device(target).estimated and (min_conf is None
                                         or min_conf >=
                                         TRANSFER_MIN_CONFIDENCE):
        assert tier != "transfer"


# ------------------------------------------------- kernel lowering gate -----

def test_lowering_kwargs_per_backend():
    from jax.experimental.pallas import triton as pltriton
    ds = ("parallel", "parallel", "arbitrary")
    tpu = lowering_kwargs(dimension_semantics=ds, backend="tpu")
    assert "compiler_params" in tpu
    assert tuple(tpu["compiler_params"].dimension_semantics) == ds
    gpu = lowering_kwargs(dimension_semantics=ds, num_warps=4,
                          num_stages=2, backend="gpu")
    cp = gpu["compiler_params"]
    assert isinstance(cp, pltriton.CompilerParams)
    # Mosaic-only kwargs never leak across the backend boundary
    assert not hasattr(cp, "dimension_semantics")
    assert lowering_kwargs(dimension_semantics=ds, backend="cpu") == {}
    # the interpreter takes no params on any backend
    for b in BACKENDS:
        assert lowering_kwargs(dimension_semantics=ds, num_warps=4,
                               interpret=True, backend=b) == {}


@pytest.fixture()
def gpu_device(monkeypatch):
    monkeypatch.setenv("KERNEL_LAUNCHER_DEVICE", "gpu-a100")


def test_active_backend_follows_device_env(monkeypatch):
    monkeypatch.setenv("KERNEL_LAUNCHER_DEVICE", "gpu-a100")
    assert active_backend() == "gpu"
    monkeypatch.setenv("KERNEL_LAUNCHER_DEVICE", "tpu-v5e")
    assert active_backend() == "tpu"
    monkeypatch.setenv("KERNEL_LAUNCHER_DEVICE", "cpu")
    assert active_backend() == "cpu"


def test_gpu_matmul_interpret_matches_reference(rng, gpu_device):
    from repro.core import get_kernel
    b = get_kernel("matmul")
    a = rng.standard_normal((256, 512)).astype(np.float32)
    bb = rng.standard_normal((512, 256)).astype(np.float32)
    for order in ("mnk", "nmk"):
        cfg = b.default_config() | {"grid_order": order}
        ok, msg = verify_against_reference(b, cfg, [a, bb])
        assert ok, f"{order}: {msg}"


def test_gpu_stencils_interpret_match_reference(rng, gpu_device,
                                                small_fields):
    from repro.core import get_kernel
    u, v, w, evisc, scal = small_fields
    b = get_kernel("advec_u")
    ok, msg = verify_against_reference(
        b, b.default_config() | {"block_z": 4, "block_y": 8}, [u, v, w, scal])
    assert ok, msg
    b = get_kernel("diff_uvw")
    ok, msg = verify_against_reference(b, b.default_config(),
                                       [u, v, w, evisc, scal])
    assert ok, msg


def test_flash_attention_has_no_gpu_lowering(rng, gpu_device):
    from repro.core import get_kernel
    b = get_kernel("flash_attention_causal")
    q = rng.standard_normal((2, 256, 128)).astype(np.float32)
    with pytest.raises(NotImplementedError, match="GPU"):
        b.make(b.default_config(), (q, q, q))


def test_ops_attention_falls_back_on_gpu(rng, gpu_device, monkeypatch):
    # even with the Pallas backend forced, the router must not pick the
    # TPU-only flash kernel on a GPU device — the jnp oracle serves
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.ops import attention
    q = jnp.asarray(rng.standard_normal((1, 2, 256, 128)),
                    dtype=jnp.float32)
    out = attention(q, q, q, causal=True)
    expected = ref.attention_ref(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=1e-5, atol=1e-5)


def test_gpu_tuning_space_records_and_scores(tmp_path, gpu_device):
    # tunebench end-to-end on the GPU device: the cost model picks up
    # the tensor-core granule / vector ratio and the space records
    from repro.core import get_kernel
    from repro.tunebench import record_space
    ds = record_space(get_kernel("matmul"), (256, 256, 256), "float32",
                      "gpu-a100")
    assert ds.best() is not None
    assert ds.device_kind == "gpu-a100"
    shipped = SpaceDataset.load(
        DATASET_DIR / "matmul--gpu-a100--256x256x256--float32.space.json")
    assert shipped.best().config == ds.best().config


# --------------------------------------------------- profiler annotation ----

def test_profile_marks_estimated_devices():
    from repro.core import get_kernel
    from repro.prof.profile import KernelProfile, profile_from_workload
    b = get_kernel("matmul")
    w = b.make_workload(b.default_config(), (256, 256, 256), "float32")
    known = profile_from_workload(w, get_device("gpu-a100"), "float32",
                                  100.0, kernel="matmul",
                                  problem_size=(256, 256, 256))
    assert not known.estimated
    assert "estimated" not in known.to_json()   # byte-compat for known HW
    guessed = profile_from_workload(w, get_device("gpu-h100"), "float32",
                                    100.0, kernel="matmul",
                                    problem_size=(256, 256, 256))
    assert guessed.estimated
    doc = guessed.to_json()
    assert doc["estimated"] is True
    assert KernelProfile.from_json(doc).estimated
