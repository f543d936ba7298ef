"""Tuner strategies + cost model."""

import numpy as np
import pytest

from repro.core import ConfigSpace, Workload, get_device, get_kernel
from repro.tuner import (CostModel, CostModelEvaluator, Evaluation,
                         evaluation_from_json, evaluation_to_json,
                         tune_anneal, tune_bayes, tune_exhaustive,
                         tune_random)
from repro.tuner.runner import EvalResult


def quadratic_space():
    """Known landscape: score = (x-5)^2 + (y-3)^2 + 1, minimum at (5,3)."""
    s = ConfigSpace()
    s.tune("x", tuple(range(10)))
    s.tune("y", tuple(range(10)))

    def evaluate(cfg):
        v = (cfg["x"] - 5) ** 2 + (cfg["y"] - 3) ** 2 + 1.0
        return EvalResult(score_us=float(v), feasible=True)

    return s, evaluate


@pytest.mark.parametrize("strategy", [tune_random, tune_bayes, tune_anneal])
def test_strategies_find_optimum_region(strategy):
    s, ev = quadratic_space()
    res = strategy(s, ev, max_evals=60, rng=np.random.default_rng(0))
    assert res.best_score_us <= 3.0  # within the optimum's neighborhood


def test_exhaustive_finds_exact_optimum():
    s, ev = quadratic_space()
    res = tune_exhaustive(s, ev, limit=1000)
    assert res.best_score_us == 1.0
    assert res.best_config == {"x": 5, "y": 3}


def test_bayes_beats_random_on_average():
    """Paper C4-lite: Bayesian optimization converges faster than random
    on the real kernel landscape (advec_u cost model)."""
    b = get_kernel("advec_u")
    wins = 0
    trials = 5
    for seed in range(trials):
        ev = CostModelEvaluator(b, (256, 256, 256), "float32",
                                get_device("tpu-v5e"), verify="none")
        r_r = tune_random(b.space, ev, max_evals=40,
                          rng=np.random.default_rng(seed))
        r_b = tune_bayes(b.space, ev, max_evals=40,
                         rng=np.random.default_rng(seed))
        if r_b.best_score_us <= r_r.best_score_us:
            wins += 1
    assert wins >= 3


def test_trajectory_monotone():
    s, ev = quadratic_space()
    res = tune_random(s, ev, max_evals=50, rng=np.random.default_rng(1))
    traj = res.trajectory()
    scores = [b for _, b in traj]
    assert scores == sorted(scores, reverse=True)


def test_dedup_same_config_not_reevaluated():
    s, _ = quadratic_space()
    calls = []

    def ev(cfg):
        calls.append(dict(cfg))
        return EvalResult(1.0, True)

    tune_anneal(s, ev, max_evals=30, rng=np.random.default_rng(0))
    keys = [tuple(sorted(c.items())) for c in calls]
    assert len(keys) == len(set(keys))


# ----------------------- warm start (fleet resume) -----------------------


class _Interrupted(Exception):
    pass


def _crash_then_resume(strategy, max_evals=40, crash_after=13, seed=7):
    """Run ``strategy`` three ways on the quadratic landscape: straight
    through, killed mid-session (the fleet worker's crash path: the log
    records every measured config, including the one whose result the
    session never saw), and resumed from the serialized log."""
    s, ev = quadratic_space()

    full_calls = []

    def ev_full(cfg):
        full_calls.append(s.freeze(cfg))
        return ev(cfg)

    full = strategy(s, ev_full, max_evals=max_evals,
                    rng=np.random.default_rng(seed))

    log = []

    def ev_crash(cfg):
        r = ev(cfg)
        log.append(Evaluation(config=dict(cfg), score_us=r.score_us,
                              feasible=r.feasible, wall_s=0.0,
                              error=r.error))
        if len(log) >= crash_after:
            raise _Interrupted
        return r

    with pytest.raises(_Interrupted):
        strategy(s, ev_crash, max_evals=max_evals,
                 rng=np.random.default_rng(seed))

    history = [evaluation_from_json(d)                 # disk round-trip
               for d in [evaluation_to_json(e) for e in log]]
    resumed_calls = []

    def ev_resumed(cfg):
        resumed_calls.append(s.freeze(cfg))
        return ev(cfg)

    resumed = strategy(s, ev_resumed, max_evals=max_evals,
                       rng=np.random.default_rng(seed),
                       history=history)
    return s, full, full_calls, log, resumed, resumed_calls


@pytest.mark.parametrize("strategy",
                         [tune_bayes, tune_anneal, tune_random])
def test_warm_start_resume_is_deterministic(strategy):
    """ISSUE 3 satellite: resuming from a serialized history with the
    same seed must visit exactly the configs an uninterrupted run would
    have visited after the crash point — no re-measurement, no drift."""
    s, full, full_calls, log, resumed, resumed_calls = \
        _crash_then_resume(strategy)
    k = len(log)
    # the interrupted prefix matches the uninterrupted run
    assert [s.freeze(e.config) for e in log] == full_calls[:k]
    # the resume measures exactly the remaining configs, in order
    assert resumed_calls == full_calls[k:]
    # and the final session state is identical
    assert [s.freeze(e.config) for e in resumed.evaluations] \
        == [s.freeze(e.config) for e in full.evaluations]
    assert resumed.best_config == full.best_config
    assert resumed.best_score_us == full.best_score_us


def test_warm_start_exhaustive_skips_measured_prefix():
    s, ev = quadratic_space()
    calls = []

    def ev_live(cfg):
        calls.append(s.freeze(cfg))
        return ev(cfg)

    head = [c for _, c in zip(range(30), s.enumerate())]
    history = [Evaluation(config=dict(c),
                          score_us=float((c["x"] - 5) ** 2
                                         + (c["y"] - 3) ** 2 + 1.0),
                          feasible=True, wall_s=0.0) for c in head]
    res = tune_exhaustive(s, ev_live, limit=1000, history=history)
    assert len(calls) == 100 - 30                  # prefix replayed free
    assert len(res.evaluations) == 100
    assert res.best_config == {"x": 5, "y": 3}


# ------------------------------ cost model ------------------------------


def test_cost_model_vmem_spill_then_infeasible():
    dev = get_device("tpu-v5e")
    m = CostModel(dev, noise_sigma=0)
    fit = Workload(flops=1e9, hbm_bytes=1e6, vmem_bytes=1024, grid=1)
    spill = Workload(flops=1e9, hbm_bytes=1e6,
                     vmem_bytes=int(dev.vmem_bytes * 1.5), grid=1)
    blown = Workload(flops=1e9, hbm_bytes=1e6,
                     vmem_bytes=int(dev.vmem_bytes * 4.5), grid=1)
    t_fit = m.time(fit, "float32")
    t_spill = m.time(spill, "float32")
    assert np.isfinite(t_fit) and np.isfinite(t_spill)
    assert t_spill > t_fit          # spilling degrades
    assert not np.isfinite(m.time(blown, "float32"))


def test_cost_model_monotone_in_flops_and_bytes():
    m = CostModel(get_device("tpu-v5e"), noise_sigma=0)
    base = dict(hbm_bytes=1e9, vmem_bytes=1024, grid=16)
    t1 = m.time(Workload(flops=1e12, **base), "bfloat16")
    t2 = m.time(Workload(flops=4e12, **base), "bfloat16")
    assert t2 > t1
    t3 = m.time(Workload(flops=1e9, hbm_bytes=1e9, vmem_bytes=1024,
                         grid=16), "bfloat16")
    t4 = m.time(Workload(flops=1e9, hbm_bytes=8e9, vmem_bytes=1024,
                         grid=16), "bfloat16")
    assert t4 > t3


def test_cost_model_f32_slower_than_bf16_when_compute_bound():
    m = CostModel(get_device("tpu-v5e"), noise_sigma=0)
    w = Workload(flops=1e13, hbm_bytes=1e6, vmem_bytes=1024, grid=1,
                 mxu_tile=(256, 256, 256))
    assert m.time(w, "float32") > m.time(w, "bfloat16")


def test_cost_model_alignment_penalty():
    m = CostModel(get_device("tpu-v5e"), noise_sigma=0)
    base = dict(flops=1e13, hbm_bytes=1e6, vmem_bytes=1024, grid=1)
    aligned = m.time(Workload(**base, mxu_tile=(256, 256, 256)), "bfloat16")
    ragged = m.time(Workload(**base, mxu_tile=(130, 257, 256)), "bfloat16")
    assert ragged > aligned


def test_cost_model_noise_deterministic():
    m = CostModel(get_device("tpu-v5e"))
    w = Workload(flops=1e12, hbm_bytes=1e9, vmem_bytes=1024, grid=4)
    a = m.time(w, "float32", noise_key="k1")
    b = m.time(w, "float32", noise_key="k1")
    c = m.time(w, "float32", noise_key="k2")
    assert a == b
    assert a != c


def test_wallclock_evaluator_refuses_without_a_tpu():
    from repro.tuner import WallClockEvaluator
    b = get_kernel("matmul")
    args = [np.ones((128, 128), np.float32)] * 2
    with pytest.raises(RuntimeError, match="times the TPU"):
        WallClockEvaluator(b, args)
    # the interpreter stays available when asked for by name
    ev = WallClockEvaluator(b, args, interpret=True, repeats=1)
    r = ev(b.default_config())
    assert r.feasible and r.verified, r.error
