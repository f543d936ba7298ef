"""Crash-isolated sandbox + correctness oracle (ISSUE 7).

Covers the acceptance criteria: every injected fault mode (hang, raise,
segfault, allocation bomb, wrong output) maps onto its structured
verdict without killing the parent process, and a fast-but-wrong config
is rejected by all three wisdom promotion paths — online hot-swap,
fleet shard-winner assembly, and transfer record minting.
"""

import pytest

from repro.core.registry import register, unregister
from repro.core.wisdom import Wisdom
from repro.core.wisdom_kernel import WisdomKernel
from repro.distrib import MemoryTransport
from repro.distrib.sync import transport_wisdom
from repro.fleet import ControlBus, Coordinator, TuningJob, job_id_for
from repro.fleet.jobs import lease_name
from repro.online.promotion import PromotionPipeline
from repro.sandbox import (FAULT_PARAM, CorrectnessOracle, FaultyEvaluator,
                           OracleGate, SandboxedEvaluator, SandboxSettings,
                           SandboxVerdict, clear_verdict_cache,
                           make_faulty_kernel, memory_ceiling,
                           sandboxed_call)
from repro.sandbox.demo import run_demo
from repro.transfer.predictor import TransferPrediction, TransferResult

WRONG = {"scale": 1, FAULT_PARAM: "wrong"}
HONEST = {"scale": 1, FAULT_PARAM: "none"}
PROBLEM = (8, 8)
DTYPE = "float32"
DEVICE = "tpu-v5e"


def _fork(timeout_s=10.0):
    """Fork settings with a generous default ceiling: forking a parent
    that a long test session has grown to multi-GB RSS costs real time
    (page-table copy), so only the hang tests — where hitting the
    ceiling IS the assertion — use a short timeout."""
    return SandboxSettings(timeout_s=timeout_s,
                           memory_bytes=memory_ceiling(128 * 2**20))


@pytest.fixture()
def faulty():
    b = make_faulty_kernel(hang_s=3600.0)
    register(b)
    clear_verdict_cache()
    yield b
    unregister(b.name)
    clear_verdict_cache()


# ------------------------------ the sandbox ----------------------------------

def test_sandboxed_call_returns_payload():
    verdict, out = sandboxed_call(lambda: 41 + 1, _fork())
    assert verdict.ok and verdict.status == "ok"
    assert out == 42
    assert verdict.wall_s >= 0.0


@pytest.mark.parametrize("mode,status", [
    ("none", "ok"),
    ("raise", "crash"),
    ("segv", "crash"),
    ("oom", "oom"),
    ("hang", "timeout"),
])
def test_fault_modes_map_to_verdicts(mode, status):
    ev = SandboxedEvaluator(FaultyEvaluator(hang_s=3600.0),
                            _fork(1.0 if mode == "hang" else 10.0))
    result = ev({"scale": 1, FAULT_PARAM: mode})
    _config, verdict = ev.verdicts[-1]
    assert verdict.status == status
    assert result.info["sandbox"] == status
    if mode == "none":
        assert result.feasible and result.score_us == pytest.approx(101.0)
    else:
        assert not result.feasible
        assert result.error.startswith(f"sandbox:{status}")
    if mode == "segv":
        assert verdict.exit_cause.startswith("signal:")
    if mode == "hang":
        assert verdict.exit_cause == "killed:timeout"


def test_hang_times_out_without_killing_parent():
    """Acceptance: an injected hang is killed at the wall-clock ceiling
    and the parent carries on evaluating."""
    ev = SandboxedEvaluator(FaultyEvaluator(hang_s=3600.0),
                            _fork(timeout_s=1.0))
    hung = ev({"scale": 1, FAULT_PARAM: "hang"})
    assert not hung.feasible and hung.info["sandbox"] == "timeout"
    assert hung.info["wall_s"] < 30.0
    # the parent is fine: the very next evaluation succeeds
    after = SandboxedEvaluator(FaultyEvaluator(hang_s=3600.0),
                               _fork())(HONEST)
    assert after.feasible


def test_inline_sandbox_maps_exceptions_to_verdicts():
    def boom():
        raise RuntimeError("nope")

    verdict, out = sandboxed_call(boom, SandboxSettings(method="inline"))
    assert verdict.status == "crash" and out is None
    assert "RuntimeError" in verdict.detail
    assert verdict.exit_cause == "exception:RuntimeError"

    def hungry():
        raise MemoryError

    verdict, _ = sandboxed_call(hungry, SandboxSettings(method="inline"))
    assert verdict.status == "oom"


def test_verdict_json_roundtrip():
    v = SandboxVerdict("numerics-mismatch", detail="allclose failed",
                       exit_cause="inline", wall_s=0.25,
                       max_err=0.3, rtol=1e-5, atol=1e-5)
    back = SandboxVerdict.from_json(v.to_json())
    assert back == v
    assert not v.ok
    with pytest.raises(ValueError):
        SandboxVerdict("not-a-status")


def test_sandboxed_evaluator_records_to_dataset(faulty):
    from repro.tunebench import SpaceDataset
    ds = SpaceDataset(faulty.name, faulty.space, PROBLEM, DTYPE, DEVICE)
    ev = SandboxedEvaluator(FaultyEvaluator(hang_s=3600.0), _fork(),
                            record_to=ds)
    ev(HONEST)
    ev({"scale": 1, FAULT_PARAM: "raise"})
    ok = ds.lookup(HONEST)
    bad = ds.lookup({"scale": 1, FAULT_PARAM: "raise"})
    assert ok.feasible and ok.verdict == ""         # "ok" is not stored
    assert not bad.feasible and bad.verdict == "crash"
    assert bad.error.startswith("sandbox:crash")
    # the verdict survives the JSON round trip, and plain entries keep
    # their original byte layout (no verdict key at all)
    again = SpaceDataset.from_doc(ds.to_doc())
    assert again.lookup(bad.config).verdict == "crash"
    assert "verdict" not in ok.to_json()


# ------------------------------ the oracle -----------------------------------

def test_oracle_classifies_wrong_output(faulty):
    oracle = CorrectnessOracle(faulty,
                               faulty.make_probe_args(PROBLEM, DTYPE))
    good = oracle.check(HONEST)
    assert good.ok and good.max_err is not None
    assert good.rtol == good.atol == 1e-5
    wrong = oracle.check(WRONG)
    assert wrong.status == "numerics-mismatch"
    assert wrong.max_err > 0.0
    # verdicts are cached per frozen config
    assert oracle.check(WRONG) is wrong


def test_gate_unverifiable_policy():
    gate = OracleGate()
    verdict = gate.check("no-such-kernel", {}, (4,), DTYPE)
    assert verdict.status == "unverifiable"
    assert gate.allows(verdict)                     # default: allow
    strict = OracleGate(on_unverifiable="reject")
    assert not strict.allows(verdict)
    with pytest.raises(ValueError):
        OracleGate(on_unverifiable="maybe")
    # unverifiable (and failing) verdicts never stamp provenance
    assert "verified" not in gate.stamp({}, "k", verdict)


def test_gate_stamps_and_caches_across_instances(faulty):
    gate = OracleGate()
    verdict = gate.check(faulty, HONEST, PROBLEM, DTYPE)
    assert verdict.ok
    stamped = gate.stamp({"strategy": "online"}, faulty.name, verdict)
    assert stamped["verified"] == {"rtol": 1e-5, "atol": 1e-5,
                                   "ref": f"{faulty.name}.reference"}
    assert stamped["strategy"] == "online"
    # the verdict cache is process-wide: a fresh gate answers from it
    # without ever building an oracle (no probe args materialized)
    other = OracleGate()
    assert other.check(faulty, HONEST, PROBLEM, DTYPE) is verdict
    assert other._oracles == {}


# --------------------- promotion paths reject wrong output -------------------

def test_online_promotion_rejects_wrong_winner(faulty, tmp_path):
    kernel = WisdomKernel(faulty, wisdom_dir=tmp_path,
                          device_kind=DEVICE)
    pipeline = PromotionPipeline(kernel, wisdom_dir=tmp_path)
    vetoed = pipeline.promote(DEVICE, PROBLEM, DTYPE, WRONG,
                              score_us=50.5, incumbent_score_us=200.0,
                              n_measurements=3, evals=16,
                              objective="costmodel")
    assert vetoed is None
    assert len(pipeline.rejections) == 1
    rejection = pipeline.rejections[0]
    assert rejection.verdict.status == "numerics-mismatch"
    assert rejection.config == WRONG
    # the wisdom file never saw the wrong config
    assert Wisdom.load(faulty.name, tmp_path).records == []

    promoted = pipeline.promote(DEVICE, PROBLEM, DTYPE, HONEST,
                                score_us=101.0, incumbent_score_us=200.0,
                                n_measurements=3, evals=16,
                                objective="costmodel")
    assert promoted is not None
    assert promoted.record.provenance["verified"]["ref"] == \
        f"{faulty.name}.reference"
    assert promoted.record.oracle_verified() is not None
    records = Wisdom.load(faulty.name, tmp_path).records
    assert [r.config[FAULT_PARAM] for r in records] == ["none"]


def test_fleet_assembly_rejects_wrong_shard_winner(faulty):
    bus = ControlBus(MemoryTransport())
    coord = Coordinator(bus, n_shards=2)
    key = (DEVICE, PROBLEM, DTYPE)
    job = TuningJob(job_id=job_id_for(faulty.name, key),
                    kernel=faulty.name, device_kind=DEVICE,
                    problem=PROBLEM, dtype=DTYPE, n_shards=2, misses=5)
    bus.publish("job", job.job_id, job.to_json())
    for shard, config, score in (("s000", WRONG, 50.5),
                                 ("s001", HONEST, 101.0)):
        bus.publish("result", lease_name(job.job_id, shard), {
            "job": job.job_id, "shard": shard, "worker": "t",
            "strategy": "exhaustive", "evals": 8, "feasible_evals": 8,
            "best_config": dict(config), "best_score_us": score})
    records = coord.assemble()
    # the wrong config won the cross-shard comparison but the oracle
    # vetoed it; the honest runner-up was assembled instead
    assert len(records) == 1
    assert records[0].config == HONEST
    assert records[0].provenance["verified"]["ref"] == \
        f"{faulty.name}.reference"
    done = bus.fetch("done", job.job_id)
    assert done["state"] == "assembled"
    assert [r["config"][FAULT_PARAM] for r in done["rejected"]] == ["wrong"]
    assert done["rejected"][0]["verdict"]["status"] == "numerics-mismatch"
    fleet = transport_wisdom(bus.transport, faulty.name).records
    assert [r.config[FAULT_PARAM] for r in fleet] == ["none"]


def test_transfer_record_falls_back_past_wrong_prediction(faulty):
    def pred(config, us):
        return TransferPrediction(config=dict(config), source_us=us,
                                  smoothed_us=us, rank_us=us,
                                  predicted_us=us)

    result = TransferResult(
        kernel=faulty.name, source_device="tpu-v4", target_device=DEVICE,
        problem_size=PROBLEM, dtype=DTYPE,
        predictions=[pred(WRONG, 50.5), pred(HONEST, 101.0)],
        confidence=0.9, components={"entries": 2,
                                    "calibration": "workload"})
    gate = OracleGate()
    record = result.record(gate=gate)
    assert record.config == HONEST
    assert record.score_us == pytest.approx(101.0)
    assert record.provenance["verified"]["ref"] == \
        f"{faulty.name}.reference"
    # ungated minting still returns the (wrong) top prediction — the
    # gate is what protects the serving path
    assert result.record().config == WRONG
    # a result whose every prediction fails verification refuses to mint
    all_wrong = TransferResult(
        kernel=faulty.name, source_device="tpu-v4", target_device=DEVICE,
        problem_size=PROBLEM, dtype=DTYPE,
        predictions=[pred(WRONG, 50.5)],
        confidence=0.9, components={"entries": 1,
                                    "calibration": "workload"})
    with pytest.raises(ValueError, match="correctness oracle"):
        all_wrong.record(gate=gate)


def test_demo_gauntlet_passes():
    """The CI smoke in-process: inject every fault, run all three
    promotion paths, demand zero bad promotions."""
    report = run_demo(timeout_s=5.0)
    assert report["problems"] == []
    assert report["bad_promotions"] == 0
    assert report["pass"] is True
    assert report["sandbox"]["hang"]["status"] == "timeout"
    assert report["sandbox"]["segv"]["exit_cause"].startswith("signal:")
    assert report["oracle"]["wrong"]["status"] == "numerics-mismatch"


def test_fork_sandbox_refuses_once_an_accelerator_is_live(monkeypatch):
    import repro.sandbox.evaluator as ev
    monkeypatch.setattr(ev, "live_accelerator_backends", lambda: ["tpu"])
    with pytest.raises(RuntimeError, match="forked child cannot use"):
        sandboxed_call(lambda: 1, _fork())
    # inline evaluation stays available on the chip
    verdict, out = sandboxed_call(lambda: 1, SandboxSettings(method="inline"))
    assert verdict.ok and out == 1


def test_live_accelerator_backends_sees_only_cpu_here():
    from repro.sandbox.evaluator import live_accelerator_backends
    assert live_accelerator_backends() == []
