"""Capture (paper §4.2) + replay tuning (paper §4.3)."""

import numpy as np
import pytest

from repro.core import (CAPTURE_ENV, WisdomKernel, capture_requested,
                        get_kernel, list_captures, load_capture,
                        write_capture)
from repro.tuner import tune_capture


def test_capture_env_gating(monkeypatch):
    monkeypatch.delenv(CAPTURE_ENV, raising=False)
    assert not capture_requested("advec_u")
    monkeypatch.setenv(CAPTURE_ENV, "advec_u,matmul")
    assert capture_requested("advec_u")
    assert capture_requested("matmul")
    assert not capture_requested("diff_uvw")
    monkeypatch.setenv(CAPTURE_ENV, "*")
    assert capture_requested("anything")


def test_capture_roundtrip(capture_dir, small_fields):
    u, v, w, _, scal = small_fields
    path = write_capture("advec_u", (32, 32, 128), "float32",
                         [u, v, w, scal])
    cap = load_capture(path)
    assert cap.kernel_name == "advec_u"
    assert cap.problem_size == (32, 32, 128)
    assert len(cap.args) == 4
    np.testing.assert_array_equal(cap.args[0], u)
    assert cap.nbytes == sum(a.nbytes for a in [u, v, w, scal])
    assert cap.meta["capture_seconds"] > 0


def test_launch_captures_when_requested(monkeypatch, capture_dir,
                                        wisdom_dir, small_fields):
    u, v, w, _, scal = small_fields
    monkeypatch.setenv(CAPTURE_ENV, "advec_u")
    k = WisdomKernel(get_kernel("advec_u"), wisdom_dir=wisdom_dir,
                     device_kind="tpu-v5e", backend="reference")
    k(u, v, w, scal)
    caps = list_captures(capture_dir)
    assert len(caps) == 1
    assert "advec_u-32x32x128-float32" in caps[0].name


def test_tune_capture_end_to_end(monkeypatch, capture_dir, wisdom_dir,
                                 small_fields):
    """The paper's full loop: capture -> replay-tune -> wisdom -> runtime
    selection picks the tuned config."""
    u, v, w, _, scal = small_fields
    monkeypatch.setenv(CAPTURE_ENV, "advec_u")
    k = WisdomKernel(get_kernel("advec_u"), wisdom_dir=wisdom_dir,
                     device_kind="tpu-v5e", backend="reference")
    k(u, v, w, scal)
    assert k.stats[-1].tier == "default"
    monkeypatch.delenv(CAPTURE_ENV)

    cap = list_captures(capture_dir)[0]
    res = tune_capture(cap, "tpu-v5e", strategy="random", max_evals=40,
                       wisdom_dir=wisdom_dir, time_budget_s=30)
    assert res.best_config is not None
    assert np.isfinite(res.best_score_us)
    # every feasible evaluation was verified or scored
    assert len(res.evaluations) >= 30

    k.invalidate()
    k(u, v, w, scal)
    assert k.stats[-1].tier == "exact"
    assert k.stats[-1].config == res.best_config


def test_tuned_config_beats_default_on_simulated_device(
        monkeypatch, capture_dir, wisdom_dir, small_fields):
    u, v, w, _, scal = small_fields
    from repro.tuner import CostModelEvaluator
    from repro.core import get_device
    b = get_kernel("advec_u")
    # the captured scenario: the default is scored where it is replaced
    ev = CostModelEvaluator(b, u.shape, "float32",
                            get_device("tpu-v5e"), verify="none")
    default_score = ev(b.default_config()).score_us
    monkeypatch.setenv(CAPTURE_ENV, "advec_u")
    k = WisdomKernel(b, wisdom_dir=wisdom_dir, device_kind="tpu-v5e",
                     backend="reference")
    k(u, v, w, scal)
    monkeypatch.delenv(CAPTURE_ENV)
    res = tune_capture(list_captures(capture_dir)[0], "tpu-v5e",
                       strategy="bayes", max_evals=60,
                       wisdom_dir=wisdom_dir, time_budget_s=60)
    assert res.best_score_us <= default_score
