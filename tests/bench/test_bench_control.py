"""The correctness check has teeth: the control (the reference one
precision below the configuration's, in the program's place) fails a
limit, and a run whose timed path is broken underneath comes out not
correct. Driven on the CPU at tiny sizes; the readings the limits were
set from were taken on the chip at each cell's own size (PERF.md)."""

from __future__ import annotations

import numpy as np
import pytest

from bench import control, harness

from bench_tiny import SERVE_SECONDS

STENCIL_CELLS = ["microhh-512-f32", "microhh-256-bf16"]
SERVE_CELLS = ["serve-decode-heavy"]


@pytest.mark.parametrize("cell", STENCIL_CELLS)
def test_the_control_fails_a_limit_the_program_meets(tiny, cell):
    lim = harness.limits(cell)
    r = control.readings(cell, 2 ** 32 + 9, 0.5)
    assert all(v <= lim[k] for k, v in r["program"].items()), r
    assert any(r["control"][k] > lim[k] for k in lim), r


def test_the_serving_control_reads_far_above_the_program(tiny):
    # logit gaps scale with the model, so at this size the control is held
    # against the program's own reading, not the cell's limit
    cell = SERVE_CELLS[0]
    r = control.readings(cell, 2 ** 32 + 9, SERVE_SECONDS)
    assert r["program"]["logit_gap"] <= harness.limits(cell)["logit_gap"]
    assert r["control"]["logit_gap"] > 3 * r["program"]["logit_gap"], r


@pytest.mark.parametrize("cell", STENCIL_CELLS + SERVE_CELLS)
def test_a_sound_run_is_correct(tiny, cell):
    res = tiny(cell, seconds=SERVE_SECONDS if cell in SERVE_CELLS else 0.5)
    assert res["correct"] and res["failed"] == 0
    assert list(res)[-1] == "compared"


def _broken_kernel(monkeypatch, kernel, corrupt):
    from repro.core.wisdom_kernel import WisdomKernel
    real = WisdomKernel.__call__

    def call(self, *args, **kw):
        out = real(self, *args, **kw)
        return corrupt(out) if self.builder.name == kernel else out

    monkeypatch.setattr(WisdomKernel, "__call__", call)


def _broken_driver(monkeypatch, **attrs):
    real = harness.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind == "drivers":
            for k, v in attrs.items():
                setattr(mod, k, v)
        return mod

    monkeypatch.setattr(harness, "load_module", load)


def _unchanged_update(dt):
    return lambda u, v, w, *tendencies: (u, v, w)


@pytest.mark.parametrize("cell", STENCIL_CELLS)
@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out",
                                   "state_unchanged"])
def test_a_broken_stencil_step_is_not_correct(tiny, monkeypatch, cell,
                                              fault):
    if fault == "answer_altered":
        _broken_kernel(monkeypatch, "advec_u",
                       lambda ut: ut.at[1, 2, 3].add(1.0))
    elif fault == "half_left_out":
        n = 16
        _broken_kernel(monkeypatch, "diff_uvw",
                       lambda ts: tuple(t.at[:n].set(0) for t in ts))
    else:
        _broken_driver(monkeypatch, _make_update=_unchanged_update)
    assert tiny(cell)["correct"] is False


@pytest.mark.parametrize("cell", SERVE_CELLS)
@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "qkv_bias_left_out"])
def test_a_broken_serving_step_is_not_correct(tiny, monkeypatch, cell,
                                              fault):
    from repro.models.transformer import DecoderLM
    from repro.serve.engine import ServeEngine
    if fault == "qkv_bias_left_out":
        real = harness.load_module("drivers", "serve_batch").make_params

        def make_params(cfg, seed, model):
            p = real(cfg, seed, model)
            attn = p["layers"]["attn"]
            for k in ("bq", "bk", "bv"):
                attn[k] = attn[k] * 0
            return p

        _broken_driver(monkeypatch, make_params=make_params)
    elif fault == "token_altered":
        real = ServeEngine._sample
        calls = []

        def sample(self, logits):
            out = real(self, logits)
            calls.append(1)
            if len(calls) % 4 == 0:        # every 4th step's tokens
                out[:] = np.argmin(logits, axis=-1)
            return out

        monkeypatch.setattr(ServeEngine, "_sample", sample)
    else:
        real = DecoderLM.decode_step

        def decode_step(self, p, cache, tokens):
            logits, _ = real(self, p, cache, tokens)
            return logits, dict(cache)

        monkeypatch.setattr(DecoderLM, "decode_step", decode_step)
    assert tiny(cell, seconds=SERVE_SECONDS)["correct"] is False
