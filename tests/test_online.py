"""Online autotuning: tracker, scheduler, promotion, end-to-end convergence.

The convergence test is the acceptance criterion for the subsystem: with an
*empty* wisdom dir, a WisdomKernel served with synthetic traffic must reach
a config within 5% of the offline-tuned optimum (cost-model objective,
fixed seed) in at most 300 launches, while non-trial launches keep running
the incumbent.
"""

import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Wisdom, WisdomKernel, get_device, get_kernel
from repro.online import (MISS_TIERS, OnlineTuner, OverheadBudget,
                          ScenarioTracker, TrialScheduler,
                          enable_online_tuning)
from repro.tuner.runner import CostModelEvaluator, EvalResult
from repro.tuner.strategies import tune_exhaustive

PROBLEM = (256, 256, 256)
DTYPE = "float32"
DEVICE = "tpu-v5e"


def _mm_args():
    rng = np.random.default_rng(0)
    a = rng.standard_normal((256, 256)).astype(np.float32)
    b = rng.standard_normal((256, 256)).astype(np.float32)
    return a, b


def _kernel(wisdom_dir, **kw):
    k = WisdomKernel(get_kernel("matmul"), wisdom_dir=wisdom_dir,
                     device_kind=DEVICE, backend="reference")
    svc = enable_online_tuning(k, objective="costmodel", seed=0, **kw)
    return k, svc


def _offline_best():
    builder = get_kernel("matmul")
    ev = CostModelEvaluator(builder, PROBLEM, DTYPE, get_device(DEVICE),
                            verify="none")
    return tune_exhaustive(builder.space, ev)


# --------------------------- acceptance criterion ---------------------------

def test_online_convergence_within_300_launches(wisdom_dir):
    """Empty wisdom + synthetic traffic -> within 5% of offline optimum in
    <= 300 launches; non-trial launches always run the incumbent."""
    k, svc = _kernel(wisdom_dir)
    a, b = _mm_args()
    default_cfg = k.builder.default_config()

    promoted_at = None
    for i in range(300):
        k(a, b)
        if svc.promotions() and promoted_at is None:
            promoted_at = i + 1
            break
    assert promoted_at is not None, "no promotion within 300 launches"
    assert promoted_at <= 300

    # trailing traffic runs the promoted config at tier "exact"
    for _ in range(5):
        k(a, b)
    assert all(s.tier == "exact" for s in k.stats[-5:])

    # before promotion, every non-trial launch ran the incumbent (the
    # default config here — wisdom started empty)
    pre = k.stats[:promoted_at - 1]
    for s in pre:
        if s.tier != "trial":
            assert s.tier == "default"
            assert s.config == default_cfg

    # within 5% of the exhaustive offline optimum, same objective/seeding
    off = _offline_best()
    ev = CostModelEvaluator(k.builder, PROBLEM, DTYPE, get_device(DEVICE),
                            verify="none")
    inc_cfg, tier = k.select_config(PROBLEM, DTYPE)
    assert tier == "exact"
    assert ev(inc_cfg).score_us <= off.best_score_us * 1.05


def test_promotion_writes_online_record(wisdom_dir):
    k, svc = _kernel(wisdom_dir)
    a, b = _mm_args()
    for _ in range(300):
        k(a, b)
        if svc.promotions():
            break
    assert svc.promotions()
    w = Wisdom.load("matmul", wisdom_dir)
    assert len(w.records) == 1
    rec = w.records[0]
    assert rec.device_kind == DEVICE
    assert rec.device_family == get_device(DEVICE).family
    assert rec.problem_size == PROBLEM
    assert rec.dtype == DTYPE
    assert np.isfinite(rec.score_us)
    assert k.builder.space.is_valid(rec.config)
    assert rec.provenance["strategy"] == "online"
    assert rec.provenance["online"] is True
    assert rec.provenance["objective"] == "costmodel"
    assert rec.provenance["evaluations"] > 0
    assert rec.provenance["live_measurements"] >= 1


def test_promoted_variant_is_prewarmed(wisdom_dir):
    """The hot swap must not stall the next launch on compilation."""
    k, svc = _kernel(wisdom_dir)
    a, b = _mm_args()
    for _ in range(300):
        k(a, b)
        if svc.promotions():
            break
    assert svc.promotions()
    k(a, b)
    assert k.stats[-1].tier == "exact"
    assert k.stats[-1].cached            # promotion prewarmed it
    assert k.stats[-1].compile_s == 0.0


# ------------------------------ trial behaviour ------------------------------

def test_epsilon_zero_never_trials(wisdom_dir):
    k, svc = _kernel(wisdom_dir, epsilon=0.0)
    a, b = _mm_args()
    for _ in range(60):
        k(a, b)
    assert all(s.tier != "trial" for s in k.stats)
    assert svc.meter.trials == 0
    assert not svc.promotions()          # no live confirmation -> no promo


def test_budget_caps_screens_per_launch(wisdom_dir):
    budget = OverheadBudget(per_launch_s=10.0, screens_per_launch=2)
    k, svc = _kernel(wisdom_dir, budget=budget)
    a, b = _mm_args()
    n = 40
    for _ in range(n):
        k(a, b)
    assert svc.meter.screens <= budget.screens_per_launch * n


def test_tick_advances_screening_without_launches(wisdom_dir):
    k, svc = _kernel(wisdom_dir, epsilon=0.0)
    a, b = _mm_args()
    for _ in range(4):                   # past the activation threshold
        k(a, b)
    state = svc.state(PROBLEM, DTYPE)
    assert state is not None
    before = state.scheduler.screens
    while not state.scheduler.screening_done():
        assert svc.tick() > 0
    assert state.scheduler.screens > before


# --------------------------------- tracker -----------------------------------

def test_tracker_counts_misses_and_activates():
    t = ScenarioTracker(activation_threshold=3)
    for _ in range(2):
        t.observe(DEVICE, PROBLEM, DTYPE, "default")
    assert not t.is_hot(DEVICE, PROBLEM, DTYPE)
    t.observe(DEVICE, PROBLEM, DTYPE, "device+dtype")
    assert t.is_hot(DEVICE, PROBLEM, DTYPE)
    st = t.stats(DEVICE, PROBLEM, DTYPE)
    assert st.launches == 3 and st.misses == 3


def test_tracker_exact_and_forced_are_not_misses():
    t = ScenarioTracker(activation_threshold=1)
    t.observe(DEVICE, PROBLEM, DTYPE, "exact")
    t.observe(DEVICE, PROBLEM, DTYPE, "forced")
    assert not t.is_hot(DEVICE, PROBLEM, DTYPE)
    assert t.stats(DEVICE, PROBLEM, DTYPE).misses == 0
    assert "exact" not in MISS_TIERS and "forced" not in MISS_TIERS


# ------------------------ successive halving bracket -------------------------

def test_scheduler_halving_picks_best_under_noise():
    """Wall-clock-style noisy measurements: halving still finds the truly
    best candidate of the bracket."""
    builder = get_kernel("matmul")
    ev = CostModelEvaluator(builder, PROBLEM, DTYPE, get_device(DEVICE),
                            verify="none")
    rng = np.random.default_rng(1)
    sched = TrialScheduler(builder.space, ev, rng, pool_size=32,
                           bracket_size=4)

    class _Timer:
        def take(self):
            return True

    sched.screen(_Timer())
    assert sched.screening_done()
    truth = {sched.space.freeze(m.config): m.screen_score_us
             for m in sched._bracket.members}
    best_key = min(truth, key=truth.get)
    meas_rng = np.random.default_rng(2)
    for _ in range(200):
        cand = sched.next_trial()
        if cand is None:
            break
        noisy = truth[sched.space.freeze(cand)] * meas_rng.uniform(0.97, 1.03)
        sched.report_trial(cand, noisy)
    won = sched.winner()
    assert won is not None
    cfg, score, n = won
    assert sched.space.freeze(cfg) == best_key
    assert n >= 1


# --------------------------- traced launch streams ---------------------------

def test_traced_launches_feed_tracker_and_tick_promotes(wisdom_dir):
    """Kernels launched inside an outer jit can't run live trials, but
    their trace-time selection registers demand, and tick() resolves the
    whole loop under the cost-model objective."""
    import jax

    k, svc = _kernel(wisdom_dir)
    a, b = _mm_args()

    @jax.jit
    def outer(x, y):
        return k(x, y)

    np.asarray(outer(a, b))              # one traced execution stream
    state = svc.state(PROBLEM, DTYPE)
    assert state is not None and state.traced
    assert svc.meter.trials == 0         # no live trials were interleaved

    for _ in range(500):
        svc.tick()
        if svc.promotions():
            break
    assert svc.promotions(), "tick() never resolved the traced scenario"
    rec = Wisdom.load("matmul", wisdom_dir).records[0]
    assert rec.provenance["strategy"] == "online"
    cfg, tier = k.select_config(PROBLEM, DTYPE)
    assert tier == "exact"               # the next trace selects it


def test_dead_bracket_finishes_scenario(wisdom_dir):
    """Nothing feasible in the space -> scenario finishes without
    promotion instead of spending budget forever."""
    from repro.core import KernelBuilder

    b = KernelBuilder("dead-space-kernel")
    b.tune("x", (1, 2))
    b.restriction(lambda c: False)       # no valid config exists
    b.reference(lambda v: v)
    k = WisdomKernel(b, wisdom_dir=wisdom_dir, device_kind=DEVICE,
                     backend="reference")
    svc = enable_online_tuning(k, objective="costmodel", seed=0)
    v = np.ones((4,), np.float32)
    for _ in range(10):
        k(v)
    state = svc.state((4,), DTYPE)
    assert state is not None and state.finished
    assert not svc.promotions()
    assert any(kind == "no-candidates" for kind, _, _ in svc.events)


def test_incumbent_baseline_resets_when_selection_flips(wisdom_dir):
    """Wall-clock incumbent timings must not blend two different configs."""
    k, svc = _kernel(wisdom_dir, epsilon=0.0)
    a, b = _mm_args()
    for _ in range(10):
        k(a, b)
    state = svc.state(PROBLEM, DTYPE)
    assert len(state.incumbent_runs) > 0
    state.incumbent_score_us = 123.0
    flipped = dict(state.incumbent_config)
    flipped["block_m"] = 64 if flipped["block_m"] != 64 else 128
    state.set_incumbent(k.builder.space, flipped)
    assert len(state.incumbent_runs) == 0
    assert state.incumbent_score_us is None
    # rolling window stays bounded in observe-only mode
    for _ in range(5):
        k(a, b)
    assert state.incumbent_runs.maxlen is not None


# ----------------------------- host integration ------------------------------

class _FakeTuner:
    def __init__(self):
        self.ticks = 0

    def tick(self):
        self.ticks += 1
        return 0


def test_train_step_ticks_online_during_warmup():
    from repro.optim.adamw import AdamW
    from repro.train.step import make_train_step

    class TinyModel:
        def loss(self, params, batch):
            loss = jnp.sum(params["w"] ** 2) + jnp.sum(batch["x"])
            return loss, {"loss": loss}

    svc = _FakeTuner()
    opt = AdamW(lr=1e-2)
    params = {"w": jnp.ones((4,), jnp.float32)}
    state = {"params": params, "opt": opt.init(params),
             "step": jnp.zeros((), jnp.int32)}
    step_fn = make_train_step(TinyModel(), opt, online=svc,
                              online_warmup_steps=2)
    batch = {"x": jnp.ones((2, 2), jnp.float32)}
    for _ in range(4):
        state, _ = step_fn(state, batch)
    assert svc.ticks == 2                # only the warmup steps sponsor work


# ------------------------------- env plumbing --------------------------------

def test_env_auto_attach(monkeypatch, wisdom_dir):
    monkeypatch.setenv("KERNEL_LAUNCHER_ONLINE", "1")
    k = WisdomKernel(get_kernel("matmul"), wisdom_dir=wisdom_dir,
                     device_kind=DEVICE, backend="reference")
    assert isinstance(k.online, OnlineTuner)
    monkeypatch.setenv("KERNEL_LAUNCHER_ONLINE", "0")
    k2 = WisdomKernel(get_kernel("matmul"), wisdom_dir=wisdom_dir,
                      device_kind=DEVICE, backend="reference")
    assert k2.online is None


def test_env_budget(monkeypatch):
    monkeypatch.setenv("KERNEL_LAUNCHER_ONLINE_BUDGET_MS", "5")
    monkeypatch.setenv("KERNEL_LAUNCHER_ONLINE_SCREENS", "3")
    b = OverheadBudget.from_env()
    assert b.per_launch_s == pytest.approx(5e-3)
    assert b.screens_per_launch == 3
