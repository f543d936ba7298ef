"""System-level behaviour: the paper's end-to-end claims, reproduced small.

C1  default config is far from optimum;
C2  a config tuned for one scenario transfers poorly to others;
C3  runtime selection (Kernel Launcher) achieves the per-scenario optimum
    (PPM = 1.0) while any fixed config does not;
C5  first launch pays compilation, subsequent launches are cache hits.
"""

import numpy as np
import pytest

from repro.configs.microhh import Scenario
from repro.core import WisdomKernel, get_device, get_kernel
from repro.core.builder import args_meta
from repro.tuner import CostModelEvaluator, tune_kernel, tune_random


SCENARIOS = [
    Scenario("advec_u", (32, 32, 128), "float32", "tpu-v5e"),
    Scenario("advec_u", (64, 64, 128), "float32", "tpu-v5e"),
    Scenario("advec_u", (32, 32, 128), "bfloat16", "tpu-v4"),
    Scenario("advec_u", (64, 64, 128), "float32", "tpu-v4"),
]


def evaluator(sc: Scenario) -> CostModelEvaluator:
    return CostModelEvaluator(get_kernel(sc.kernel), sc.grid, sc.dtype,
                              get_device(sc.device), verify="none")


@pytest.fixture(scope="module")
def tuned():
    """Best config per scenario (random search, fixed budget)."""
    best = {}
    for sc in SCENARIOS:
        b = get_kernel(sc.kernel)
        res = tune_random(b.space, evaluator(sc), max_evals=80,
                          rng=np.random.default_rng(hash(sc.key) % 2**31))
        best[sc.key] = (res.best_config, res.best_score_us)
    return best


def test_c1_default_far_from_optimum():
    # against the optimum itself (every valid config scored), not a
    # random search's estimate, which can miss it by more than 10 %
    b = get_kernel("advec_u")
    fracs = []
    for sc in SCENARIOS:
        ev = evaluator(sc)
        default_t = ev(b.default_config()).score_us
        best_t = min(ev(c).score_us for c in b.space.enumerate()
                     if b.space.is_valid(c))
        assert best_t < default_t, sc.key
        fracs.append(best_t / default_t)
    assert np.mean(fracs) < 0.9   # tuning buys >10% on average


def test_c2_single_scenario_config_not_portable(tuned):
    """The config tuned for scenario 0 is suboptimal elsewhere."""
    donor_cfg = tuned[SCENARIOS[0].key][0]
    worse = 0
    for sc in SCENARIOS[1:]:
        t_donor = evaluator(sc)(donor_cfg).score_us
        t_best = tuned[sc.key][1]
        if t_donor > t_best * 1.02:
            worse += 1
    assert worse >= 2, "transferred config should be suboptimal somewhere"


def test_c3_runtime_selection_achieves_optimum(tmp_path, tuned):
    """Wisdom-backed runtime selection hits the per-scenario best (PPM=1)."""
    for sc in SCENARIOS:
        tune_kernel(get_kernel(sc.kernel), sc.grid, sc.dtype, sc.device,
                    strategy="random", max_evals=80,
                    time_budget_s=60, wisdom_dir=tmp_path,
                    seed=hash(sc.key) % 2**31)
    for sc in SCENARIOS:
        k = WisdomKernel(get_kernel(sc.kernel), wisdom_dir=tmp_path,
                         device_kind=sc.device)
        cfg, tier = k.select_config(sc.grid, sc.dtype)
        assert tier == "exact", sc.key
        t_sel = evaluator(sc)(cfg).score_us
        # wisdom stores the best seen under the same budget regime
        assert t_sel <= tuned[sc.key][1] * 1.25


def test_c5_first_launch_compiles_then_caches(wisdom_dir, small_fields):
    u, v, w, _, scal = small_fields
    k = WisdomKernel(get_kernel("advec_u"), wisdom_dir=wisdom_dir,
                     device_kind="tpu-v5e", backend="reference")
    k(u, v, w, scal)
    k(u, v, w, scal)
    first, second = k.stats[0], k.stats[1]
    assert not first.cached and second.cached
    assert first.compile_s > 0 and second.compile_s == 0
    # new problem size -> new compilation (paper §4.5)
    u2, v2, w2 = u[:8], v[:8], w[:8]
    k(u2, v2, w2, scal)
    assert not k.stats[2].cached


@pytest.mark.parametrize("backend,maker", [("reference", "make_reference"),
                                           ("interpret", "make")])
def test_c5_a_cache_hit_builds_nothing(wisdom_dir, small_fields, monkeypatch,
                                       backend, maker):
    """The kernel is built on a miss only; a hit selects and dispatches."""
    u, v, w, _, scal = small_fields
    builder = get_kernel("advec_u")
    k = WisdomKernel(builder, wisdom_dir=wisdom_dir, device_kind="tpu-v5e",
                     backend=backend)
    calls = {"_instantiate": 0, maker: 0}

    def counted(obj, name):
        real = getattr(obj, name)

        def call(*a, **kw):
            calls[name] += 1
            return real(*a, **kw)
        monkeypatch.setattr(obj, name, call)

    counted(k, "_instantiate")
    counted(builder, maker)
    k(u, v, w, scal)
    assert calls == {"_instantiate": 1, maker: 1}
    k(u, v, w, scal)
    k(u, v, w, scal)
    assert calls == {"_instantiate": 1, maker: 1}
    assert [s.cached for s in k.stats] == [False, True, True]
    # prewarming a scenario already compiled builds nothing either
    cfg = k.stats[0].config
    assert k.prewarm(args_meta(u, v, w, scal), cfg) is False
    assert calls == {"_instantiate": 1, maker: 1}


@pytest.mark.parametrize("env_set", [True, False])
def test_persistent_cache_goes_where_the_environment_says(tmp_path,
                                                          env_set):
    """JAX_COMPILATION_CACHE_DIR wins; unset, the fixed default holds the
    entries. Run in a child so this process never enables the cache."""
    import os
    import subprocess
    import sys
    env_dir, default_dir = tmp_path / "env", tmp_path / "default"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    code = ("import sys, jax, jax.numpy as jnp\n"
            "from repro.core.compile_cache import enable_persistent_cache\n"
            "print(enable_persistent_cache(sys.argv[1]))\n"
            "jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(4)).block_until_ready()\n")
    proc = subprocess.run([sys.executable, "-c", code, str(default_dir)],
                          env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr
    used, unused = ((env_dir, default_dir) if env_set
                    else (default_dir, env_dir))
    assert proc.stdout.strip() == str(used)
    assert any(used.iterdir())
    assert not unused.exists() or not any(unused.iterdir())
