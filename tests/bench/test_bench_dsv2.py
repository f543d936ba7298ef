"""The DeepSeek-V2 serving cell, driven on the CPU at tiny sizes: the
program decodes through its latent cache as the plain reference's full
forward computes; a sound run is correct and a broken one is not; the
control reads far above the program; the traffic fits the arena; the
work counts match the built model; the new readers read what a trace
and a run record hold, and nothing where the program lacks it."""

from __future__ import annotations

import dataclasses
import importlib.util

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from bench import harness, trace_reduce, work, work_mla_moe
from bench.reference import deepseek_v2_ref, stablelm_ref

from bench_tiny import ROOT, SERVE_SECONDS, merge

CELL = "serve-prompt-heavy"
CONFIG, MIX = "deepseek-v2", "azure-conv-2023"
#: tiny sizes of this cell: every width cut, 3 layers (the dense one and
#: two with experts), 5 of 8 experts held in 4 groups of 2, YaRN as
#: published; short prompt-heavy requests, so that a short window on a
#: busy CPU still finishes many
TINY = {
    ("configs", CONFIG): {
        "hidden_size": 64, "intermediate_size": 128,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "num_hidden_layers": 3, "n_routed_experts": 5,
        "num_experts_per_tok": 2, "n_group": 4, "topk_group": 2,
        "vocab_size": 512, "torch_dtype": "float32",
        "published": {"n_routed_experts": 8},
        "held_experts": {"first": 2},
        "deployment": {"n_slots": 4, "max_seq": 1024}},
    ("traffic", MIX): {"backlog": 8, "prompt_mean": 8, "output_mean": 4},
}


@pytest.fixture()
def tiny_dsv2(tiny, monkeypatch):
    """The ``tiny`` harness with this cell's tiny sizes."""
    for key, over in TINY.items():
        monkeypatch.setitem(bench_tiny.TINY, key, over)
    return tiny


def tiny_cfg() -> dict:
    return merge(harness.load_json("configs", CONFIG),
                 TINY[("configs", CONFIG)])


def _driver():
    return harness.load_module("drivers", "serve_mla_moe")


# -- the program against the reference ----------------------------------------


def test_decode_through_the_cache_matches_the_reference_forward():
    """Two sequences decoded token by token through the latent arena, the
    second admitted at cursor 3 with a stale slot below its start, against
    the reference's full forward of each: YaRN, group-limited routing and
    a held share of the experts in both."""
    from repro.models import build_model
    cfg = tiny_cfg()
    drv = _driver()
    model = build_model(drv.program_arch(cfg))
    assert model.cfg.moe.held == (2, 5) and model.cfg.moe.n_group == 4
    seed = 2 ** 33 + 7
    params = drv.make_params(cfg, seed, model)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, cfg["vocab_size"], n).astype(np.int32)
            for n in (40, 37)]
    stale = rng.integers(0, cfg["vocab_size"], 3).astype(np.int32)
    step = jax.jit(model.decode_step)
    cache = model.init_cache(2, 128)
    got = [[], []]
    for t in range(40):
        b = stale[t] if t < 3 else seqs[1][t - 3]
        cache["start"] = jnp.asarray([0, 0 if t < 3 else 3], jnp.int32)
        logits, cache = step(params, cache,
                             jnp.asarray([[seqs[0][t]], [b]], jnp.int32))
        got[0].append(np.asarray(logits[0, 0]))
        if t >= 3:
            got[1].append(np.asarray(logits[1, 0]))
    tokens = np.zeros((2, 128), np.int32)
    rows = []
    for i, s in enumerate(seqs):
        tokens[i, :len(s)] = s
        rows += [(i, p) for p in range(len(s))]
    rows = np.asarray(rows, np.int32)
    logits = np.concatenate([np.stack(g) for g in got])
    top = logits.argmax(-1).astype(np.int32)
    best, picked, first, experts = deepseek_v2_ref.logits_at(
        cfg, harness.key_from_seed(seed), tokens, rows, top)
    np.testing.assert_array_equal(first, top)
    np.testing.assert_allclose(best, logits.max(-1), rtol=2e-4, atol=2e-4)
    assert np.max(best - picked) < 2e-4
    assert len(experts) == 2
    own, used, gap = experts[0]
    assert own.shape == used.shape == (2, 128, 2) and gap.shape == (2, 128)
    np.testing.assert_array_equal(own, used)
    assert not gap.any()


def test_reference_routing_is_group_limited_and_unnormalised():
    cfg = tiny_cfg()
    probs = np.array([[0.12, 0.20, 0.05, 0.25, 0.15, 0.01, 0.18, 0.04]])
    gate, sel = deepseek_v2_ref.route(cfg, jnp.log(jnp.asarray(probs)))
    assert sorted(np.asarray(sel)[0].tolist()) == [1, 3]
    np.testing.assert_allclose(np.sort(np.asarray(gate)[0]),
                               [16 * 0.20, 16 * 0.25], rtol=1e-5)


def test_route_gap_reads_how_far_the_experts_lie_from_a_choice():
    """Logits of one token over 8 experts in 4 groups of 2, top-2 of the
    best 2 groups: the reference's own choice reads 0, and so does the
    choice after a near tie of groups; an inversion reads its width; an
    expert of a group below the best two reads its group's shortfall; a
    repeated or unknown expert reads inf."""
    cfg = tiny_cfg()
    z = jnp.asarray([[0.0, 1.0, 3.0, 2.9, 0.5, 0.2, 1.95, 1.2],
                     [0.0, 1.0, 3.0, 0.1, 2.0, 0.2, 1.99, 1.9]])
    gap = lambda row, *sel: float(deepseek_v2_ref.route_gap(  # noqa: E731
        cfg, z[row:row + 1], jnp.asarray([sel], jnp.int32))[0])
    _, own = deepseek_v2_ref.route(cfg, z)
    assert np.sort(np.asarray(own), -1).tolist() == [[2, 3], [2, 4]]
    assert gap(0, 2, 3) == 0.0 and gap(0, 3, 2) == 0.0
    assert gap(0, 2, 6) == pytest.approx(0.95)   # 3 left out, 0.95 above 6
    assert gap(0, 2, 1) == pytest.approx(1.9)    # 3 left out, 1.9 above 1
    # groups 2 and 3 tie within 0.01; after group 3, its best two are a
    # greedy choice, with expert 7 below expert 4 of the dropped group
    assert gap(1, 2, 6) == pytest.approx(0.01)
    assert gap(1, 6, 7) == pytest.approx(1.1)    # 2 left out, 1.1 above 6
    assert gap(1, 2, 5) == pytest.approx(1.8)    # group 2 scores 2.0, 5 0.2
    assert gap(0, 2, 2) == np.inf and gap(0, 2, 8) == np.inf


def test_reference_yarn_tables_follow_the_program_frequencies():
    from repro.models.common import rope_freqs
    cfg = harness.load_json("configs", CONFIG)
    cos, _ = deepseek_v2_ref.rope_tables(cfg, 2)
    arch = _driver().program_arch(cfg)
    inv = np.asarray(rope_freqs(64, 10000.0, arch.rope_scaling))
    np.testing.assert_allclose(np.arccos(np.clip(cos[1], -1, 1)),
                               np.arccos(np.cos(inv)), atol=1e-6)


# -- runs ---------------------------------------------------------------------


def test_a_sound_run_is_correct(tiny_dsv2):
    res = tiny_dsv2(CELL, seconds=SERVE_SECONDS)
    assert res["correct"] and res["failed"] == 0, res
    assert set(res["metrics"]) == {"tokens_per_s", "tpot_p95_ms", "setup_s"}


def test_a_traced_run_reports_the_expert_rows(tiny_dsv2):
    res = tiny_dsv2(CELL, seconds=SERVE_SECONDS, trace=True)
    assert res["correct"], res
    share = res["metrics"]["expert_rows_share.moe"]["value"]
    assert 0 < share <= 100
    assert {"occupancy.serve", "prompt_share.serve"} <= set(res["metrics"])


def _broken_driver(monkeypatch, **attrs):
    real = harness.load_module

    def load(kind, name):
        mod = real(kind, name)
        if kind == "drivers" and name == "serve_mla_moe":
            for k, v in attrs.items():
                setattr(mod, k, v(getattr(mod, k)))
        return mod

    monkeypatch.setattr(harness, "load_module", load)


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged",
                                   "gates_renormalised",
                                   "held_experts_left_out",
                                   "routed_over_held_only"])
def test_a_broken_serving_step_is_not_correct(tiny_dsv2, monkeypatch, fault):
    from repro.models.transformer import DecoderLM
    from repro.serve.engine import ServeEngine
    if fault == "token_altered":
        real = ServeEngine._sample
        calls = []

        def sample(self, logits):
            out = real(self, logits)
            calls.append(1)
            if len(calls) % 4 == 0:        # every 4th step's tokens
                out[:] = np.argmin(logits, axis=-1)
            return out

        monkeypatch.setattr(ServeEngine, "_sample", sample)
    elif fault == "state_unchanged":
        real = DecoderLM.decode_step

        def decode_step(self, p, cache, tokens):
            logits, _ = real(self, p, cache, tokens)
            return logits, dict(cache)

        monkeypatch.setattr(DecoderLM, "decode_step", decode_step)
    elif fault == "gates_renormalised":
        def arch(real):
            def program_arch(cfg):
                a = real(cfg)
                return dataclasses.replace(a, moe=dataclasses.replace(
                    a.moe, norm_topk=True, routed_scale=1.0))
            return program_arch
        _broken_driver(monkeypatch, program_arch=arch)
    elif fault == "routed_over_held_only":
        from repro.models import moe
        real = moe.route

        def route(e, logits):
            first, n = e.held_range
            held = (jnp.arange(e.n_routed) >= first) \
                & (jnp.arange(e.n_routed) < first + n)
            return real(e, jnp.where(held, logits, -1e9))

        monkeypatch.setattr(moe, "route", route)
    else:
        def params(real):
            def make_params(cfg, seed, model):
                p = real(cfg, seed, model)
                moe = p["layers"]["moe"]
                for k in ("w_in", "w_gate", "w_out"):
                    moe[k] = moe[k] * 0
                return p
            return make_params
        _broken_driver(monkeypatch, make_params=params)
    assert tiny_dsv2(CELL, seconds=SERVE_SECONDS)["correct"] is False


def test_the_control_reads_far_above_the_program(tiny_dsv2):
    spec = importlib.util.spec_from_file_location(
        "control_mla_moe", ROOT / "bench" / "control_mla_moe.py")
    control = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(control)
    r = control.readings(CELL, 2 ** 32 + 9, SERVE_SECONDS)
    lim = harness.limits(CELL)
    assert set(r["program"]) == set(lim)
    for name, limit in lim.items():
        assert r["program"][name] <= limit, r
    assert r["control"]["logit_gap"] > 3 * r["program"]["logit_gap"], r
    # float32 on the CPU picks the experts the reference picks
    assert r["program"]["route_gap"] == 0.0, r
    assert r["routing"]["compared"] > 0 and r["routing"]["differ"] == 0, r


def test_the_parent_program_fails_at_once(monkeypatch):
    """A program whose expert layer holds no share (as before this cell)
    is refused with a BenchError, before any weight is made."""
    import repro.models.config as config
    monkeypatch.delattr(config, "YarnCfg")
    with pytest.raises(harness.BenchError):
        _driver().program_arch(harness.load_json("configs", CONFIG))


# -- traffic ------------------------------------------------------------------


def test_the_mix_is_deterministic_and_fits_the_arena():
    params = harness.load_json("traffic", MIX)
    gen = harness.load_module("traffic", params["generator"])
    cfg = harness.load_json("configs", CONFIG)
    max_seq, vocab = cfg["deployment"]["max_seq"], cfg["vocab_size"]
    a = gen.Backlog(params, 2 ** 31 + 11, vocab, max_seq)
    b = gen.Backlog(params, 2 ** 31 + 11, vocab, max_seq)
    c = gen.Backlog(params, 3, vocab, max_seq)
    for _ in range(params["schedule_requests"] + 3):
        (pa, oa), (pb, ob), (pc, oc) = a.next(), b.next(), c.next()
        assert oa == ob == oc and len(pa) == len(pc)
        np.testing.assert_array_equal(pa, pb)
        assert len(pa) + oa <= max_seq
        assert pa.min() >= 0 and pa.max() < vocab
    prompts, outputs = gen.schedule(params, max_seq)
    wide_p, wide_o = gen.schedule(params, 10 ** 9)
    assert int((wide_p + wide_o > max_seq).sum()) == 4
    for lens, mean, median in ((prompts, params["prompt_mean"], 1020),
                               (outputs, params["output_mean"], 129)):
        assert lens.min() >= 1
        assert abs(lens.mean() / mean - 1) < 0.01
        assert abs(np.median(lens) / median - 1) < 0.03
    # about 89% of slot-steps feed prompts
    assert 0.87 < prompts.sum() / (prompts + outputs).sum() < 0.91


# -- work counts --------------------------------------------------------------


def test_param_counts_match_the_built_model():
    from repro.models import build_model
    cfg = harness.load_json("configs", CONFIG)
    model = build_model(_driver().program_arch(cfg))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    built = {str(x.dtype): 0 for x in jax.tree.leaves(shapes)}
    for x in jax.tree.leaves(shapes):
        built[str(x.dtype)] += x.size
    p = work_mla_moe.params(cfg)
    assert built["float32"] == p["float32"]
    assert built["bfloat16"] == sum(v for k, v in p.items() if k != "float32")
    stored = 2 * built["bfloat16"] + 4 * built["float32"]
    assert 9.4e9 < stored < 9.5e9
    # the held experts and the head are most of what a step streams
    step = work_mla_moe.decode_step(cfg, active=16, live=0)
    assert 8.4e9 < step.bytes < 8.5e9
    assert (2 * (p["experts"] + p["shared"]) / step.bytes) > 0.6


def test_latent_attention_counts_live_keys_at_their_width():
    cfg = harness.load_json("configs", CONFIG)
    few = work_mla_moe.latent_attention(cfg, active=16, live=16 * 100)
    many = work_mla_moe.latent_attention(cfg, active=16, live=16 * 1000)
    assert many.bytes - few.bytes == 16 * 900 * 576 * 2
    assert many.flops - few.flops == 2 * 128 * (576 + 512) * 16 * 900
    # about on the v5e's ridge: neither bound is far below the other
    pk = work.peaks("TPU v5 lite")
    ratio = (many.flops / pk.flops_per_s) / (many.bytes / pk.hbm_bytes_per_s)
    assert 0.5 < ratio < 2
    step = work_mla_moe.decode_step(cfg, active=16, live=16 * 1000)
    base = work_mla_moe.decode_step(cfg, active=16, live=0)
    assert step.bytes - base.bytes == 6 * (many.bytes
                                           - work_mla_moe.latent_attention(
                                               cfg, 16, 0).bytes)


# -- readers ------------------------------------------------------------------


def _reader(name):
    return harness.load_module("metrics", name).read


def _record(ops):
    tr = trace_reduce.Reduction(window=(0.0, 1e9), devices=1, busy_s=0.5,
                                ops=ops)
    return {"trace": tr, "config": harness.load_json("configs", CONFIG),
            "peaks": work.peaks("TPU v5 lite"),
            "serve": {"n_slots": 16,
                      "per_step": [(16, 16 * 1300, 14, True)] * 10
                      + [(16, 16 * 1300, 14, False)]}}


def test_the_roofline_reads_the_named_kernel_and_nothing_else():
    read = _reader("mla_attention_roofline")
    least = work_mla_moe.latent_attention(
        harness.load_json("configs", CONFIG), 16, 16 * 1300).least_s(
        work.peaks("TPU v5 lite"))
    rec = _record({"jit_decode_step/mla_attention.3": [50, 50 * 2 * least],
                   "jit_decode_step/mla_attention": [10, 10 * 2 * least],
                   "jit_decode_step/fusion.2": [60, 1.0]})
    assert read(rec) == pytest.approx(50.0)
    # the parent's trace: no such kernel, so nothing to read
    assert read(_record({"jit_decode_step/fusion.2": [60, 1.0]})) is None
    assert read({**_record({}), "trace": None}) is None


def test_mfu_sums_the_traced_steps_over_the_window():
    read = _reader("mfu.dsv2")
    rec = _record({})
    least = work_mla_moe.decode_step(rec["config"], 16, 16 * 1300).least_s(
        rec["peaks"])
    assert read(rec) == pytest.approx(100 * 10 * least / 1.0)
    assert read({**rec, "trace": None}) is None


def test_expert_rows_share_reads_the_tally_over_the_rows():
    read = _reader("expert_rows_share.moe")
    assert read({"experts": {"routed": 12, "rows": 1280}}) == \
        pytest.approx(100 * 12 / 1280)
    assert read({}) is None and read({"experts": {"routed": 0,
                                                  "rows": 0}}) is None


def test_the_reference_pads_each_request_to_a_power_of_two():
    reqs = [(np.zeros(100, np.int32), [1, 2]), (np.zeros(1500, np.int32), [3]),
            (np.zeros(4000, np.int32), [4] * 97)]
    assert [deepseek_v2_ref.padded_length(*r) for r in reqs] == \
        [1024, 2048, 4096]
    tokens, rows, targets = stablelm_ref.served_rows([reqs[1]], 2048)
    assert rows.tolist() == [[0, 1499]] and targets.tolist() == [3]
