"""DeepSeek-V2's published routing, held-expert shares, YaRN, and
token-level serving through the start-masked latent arena: each piece
against a hand-written case or the uncut path, on the CPU at small
sizes."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.builder import args_meta
from repro.kernels import mla_attention as mk, ops
from repro.models import build_model, moe as MO
from repro.models.common import KeyGen, rope_freqs, yarn_mscale, yarn_ramp
from repro.models.config import MoECfg
from repro.models.mla import softmax_scale
from repro.serve import Request, ServeEngine


def _dsv2(**moe):
    cfg = get_arch("deepseek-v2-236b").reduced()
    if moe:
        cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                                **moe))
    return cfg


# -- routing --


def test_group_limited_routing_and_unnormalised_gates_by_hand():
    e = MoECfg(n_routed=8, top_k=3, d_expert=4, n_group=4, topk_group=2,
               norm_topk=False, routed_scale=16.0)
    # scores: groups {0,1} {2,3} {4,5} {6,7}; group bests 0.20, 0.25,
    # 0.15, 0.18 keep groups 1 and 0; expert 4 (0.15) and 6 (0.18) lose
    # to experts of kept groups though expert 6 beats expert 0 (0.12)
    probs = np.array([0.12, 0.20, 0.05, 0.25, 0.15, 0.01, 0.18, 0.04])
    gate, sel, scores = MO.route(e, jnp.log(jnp.asarray(probs)))
    assert sorted(np.asarray(sel).tolist()) == [0, 1, 3]
    np.testing.assert_allclose(np.asarray(scores), probs, rtol=1e-6)
    want = {0: 0.12, 1: 0.20, 3: 0.25}
    for g, s in zip(np.asarray(gate), np.asarray(sel)):
        assert g == pytest.approx(16.0 * want[int(s)], rel=1e-5)
    # without groups the top 3 are 3, 1, 6; renormalised they sum to one
    flat = dataclasses.replace(e, n_group=1, topk_group=1, norm_topk=True,
                               routed_scale=1.0)
    gate, sel, _ = MO.route(flat, jnp.log(jnp.asarray(probs)))
    assert np.asarray(sel).tolist() == [3, 1, 6]
    assert float(gate.sum()) == pytest.approx(1.0, rel=1e-6)


def test_published_routing_fields():
    e = get_arch("deepseek-v2-236b").moe
    assert (e.n_routed, e.top_k, e.n_group, e.topk_group) == (160, 6, 8, 3)
    assert (e.norm_topk, e.routed_scale, e.held) == (False, 16.0, None)
    assert get_arch("deepseek-moe-16b").moe.norm_topk


@pytest.mark.parametrize("splits", [[4], [1, 3], [2, 2, 3, 1], [5, 3]])
def test_held_shares_sum_to_the_uncut_layer(splits):
    """Each rank holds a range of experts and computes only their routed
    part; with the shared experts once, the ranks sum to the uncut
    layer."""
    cfg = _dsv2()
    e = cfg.moe
    assert sum(splits) in (4, 8)
    splits = [s * e.n_routed // sum(splits) for s in splits]
    p = MO.init_moe(KeyGen(jax.random.PRNGKey(0)), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    want, aux = MO.moe_ffn(cfg, p, x)
    total = MO.shared_ffn(cfg, p, x)
    routed = 0
    first = 0
    for n in splits:
        held = dataclasses.replace(cfg, moe=dataclasses.replace(
            e, held=(first, n)))
        share = {k: (v[first:first + n] if k in ("w_in", "w_gate", "w_out")
                     else v) for k, v in p.items()}
        y, a = MO.routed_ffn(held, share, x)
        total = total + y
        routed += int(a["moe_routed"])
        assert share["w_in"].shape[0] == n
        first += n
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5, rtol=1e-5)
    # every (token, k) lands in exactly one share, none dropped
    assert routed == int(aux["moe_routed"]) == 2 * 16 * e.top_k


def test_held_layer_holds_only_its_experts():
    cfg = dataclasses.replace(get_arch("deepseek-v2-236b"),
                              moe=dataclasses.replace(
                                  get_arch("deepseek-v2-236b").moe,
                                  held=(0, 20)))
    shapes = jax.eval_shape(lambda: MO.init_moe(
        KeyGen(jax.random.PRNGKey(0)), cfg))
    assert shapes["router"].shape == (5120, 160)
    assert shapes["w_in"].shape == (20, 5120, 1536)
    assert shapes["w_out"].shape == (20, 1536, 5120)
    assert shapes["shared_in"].shape == (5120, 2 * 1536)


# -- YaRN --


def test_yarn_frequencies_follow_the_published_formula():
    y = get_arch("deepseek-v2-236b").rope_scaling
    assert yarn_ramp(64, 10000.0, y) == (10, 23)
    got = np.asarray(rope_freqs(64, 10000.0, y))
    i = np.arange(32)
    extra = 10000.0 ** (-2 * i / 64)
    inter = extra / 40

    def corr(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(10000))
    low, high = math.floor(corr(32)), math.ceil(corr(1))
    mask = 1 - np.clip((i - low) / (high - low), 0, 1)
    np.testing.assert_allclose(got, inter * (1 - mask) + extra * mask,
                               rtol=1e-6)
    assert got[0] == pytest.approx(1.0) and \
        got[-1] == pytest.approx(extra[-1] / 40, rel=1e-6)
    m = yarn_mscale(40.0, 0.707)
    assert m == pytest.approx(0.1 * 0.707 * math.log(40) + 1)
    assert softmax_scale(get_arch("deepseek-v2-236b")) == \
        pytest.approx(192 ** -0.5 * m * m)


# -- the latent kernel --

Lk, Bk, Sk, H, DV, DR, W = 2, 4, 512, 8, 64, 32, 128
CASES = {
    "pos0": (0, [0, 0, 0, 0]),
    "block_edge": (256, [0, 3, 128, 256]),
    "last": (Sk - 1, [0, 130, 400, 500]),
    "one_block": (300, [260, 290, 299, 300]),
}


def _kernel_args(rng, dtype, pos, start):
    def arr(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)
    return (arr(Lk, Bk, Sk, W), arr(Bk, H, DV, scale=0.3),
            arr(Bk, H, DR, scale=0.3), arr(Bk, W),
            jnp.asarray([1, pos], jnp.int32), jnp.asarray(start, jnp.int32),
            jnp.full((1,), (DV + DR) ** -0.5, jnp.float32))


def _by_hand(arena, q_c, q_pe, new, layer, pos, start, scale):
    """Per slot, in float64: the live keys [start, pos) of the arena and
    the step's own row at pos."""
    out = np.zeros((Bk, H, DV))
    a = np.asarray(arena, np.float64)[layer]
    q = np.concatenate([np.asarray(q_c, np.float64),
                        np.asarray(q_pe, np.float64),
                        np.zeros((Bk, H, W - DV - DR))], -1)
    for b in range(Bk):
        keys = np.concatenate([a[b, start[b]:pos],
                               np.asarray(new, np.float64)[b][None]])
        s = q[b] @ keys.T * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        out[b] = (p / p.sum(-1, keepdims=True)) @ keys[:, :DV]
    return out


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", sorted(CASES))
def test_latent_kernel_matches_its_reference(rng, case, dtype):
    pos, start = CASES[case]
    args = _kernel_args(rng, dtype, pos, start)
    want = mk.mla_attention_ref(*args)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    hand = _by_hand(*args[:4], 1, pos, start, float(args[6][0]))
    np.testing.assert_allclose(np.asarray(want), hand, atol=tol, rtol=tol)
    for config in ({"block_k": 128}, mk.builder.default_config()):
        got = mk.builder.make(config, args_meta(*args), interpret=True)(*args)
        assert got.shape == (Bk, H, DV) and got.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=tol, rtol=tol)


def test_latent_route_tally(rng, monkeypatch):
    arena, q_c, q_pe, new, _, start, _ = _kernel_args(rng, jnp.float32, 5,
                                                      [0] * Bk)

    def route(a):
        before = dict(ops.mla_decode_attention_routes)
        ops.mla_decode_attention(q_c, q_pe, a, new, 0, 5, scale=0.1,
                                 start=start)
        return {k: v - before.get(k, 0)
                for k, v in ops.mla_decode_attention_routes.items()
                if v != before.get(k, 0)}

    assert route(arena) == {"reference:backend": 1}
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert route(arena) == {"pallas": 1}
    assert route(arena[:, :, :100]) == {"reference:shape": 1}


# -- serving --


def _held_dsv2():
    """Reduced DeepSeek-V2 with YaRN, groups, and a held share of 5 of
    its 8 experts."""
    cfg = _dsv2(held=(2, 5))
    assert cfg.rope_scaling is not None and cfg.moe.n_group > 1
    return dataclasses.replace(cfg, max_seq=128)


def test_mla_stacks_serve_token_level_on_their_own():
    model = build_model(_held_dsv2())
    assert model.decode_supports_start
    params = model.init(jax.random.PRNGKey(0))
    assert ServeEngine(model, params, n_slots=2, max_seq=128).mode == "token"
    cache = model.init_cache(2, 128)
    # kv_lora 16 + d_rope 8, padded to one lane tile
    assert cache["mla"]["latent"].shape == (2, 2, 128, 128)
    assert cache["moe_routed"].dtype == jnp.int32


def _decode_alone(model, params, tokens):
    cache = model.init_cache(1, 128)
    out = []
    step = jax.jit(model.decode_step)
    for t in tokens:
        logits, cache = step(params, cache, jnp.asarray([[t]], jnp.int32))
        out.append(np.asarray(logits[0, 0]))
    return np.stack(out)


@pytest.mark.parametrize("backend", ["reference", "interpret"])
def test_requests_at_different_cursors_decode_as_alone(monkeypatch,
                                                       backend):
    """Slot 0 starts at cursor 0; slot 1 first holds another request's
    tokens, then takes a new one at cursor 5 (start masks the stale
    latents). Each slot's logits equal its request decoded alone."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    jax.clear_caches()
    model = build_model(_held_dsv2())
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    a = rng.integers(0, model.cfg.vocab, 12)
    b = rng.integers(0, model.cfg.vocab, 7)
    stale = rng.integers(0, model.cfg.vocab, 5)
    want_a = _decode_alone(model, params, a)
    want_b = _decode_alone(model, params, b)
    cache = model.init_cache(2, 128)
    step = jax.jit(model.decode_step)
    got_a, got_b = [], []
    for t in range(12):
        tok_b = stale[t] if t < 5 else b[t - 5]
        cache["start"] = jnp.asarray([0, 0 if t < 5 else 5], jnp.int32)
        logits, cache = step(params, cache,
                             jnp.asarray([[a[t]], [tok_b]], jnp.int32))
        got_a.append(np.asarray(logits[0, 0]))
        if t >= 5:
            got_b.append(np.asarray(logits[1, 0]))
    np.testing.assert_allclose(np.stack(got_a), want_a, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.stack(got_b), want_b, atol=2e-4, rtol=2e-4)
    assert int(cache["moe_routed"]) > 0


def test_token_mode_serving_matches_serving_alone():
    model = build_model(_held_dsv2())
    params = model.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(2)
    reqs = [(rng.integers(0, model.cfg.vocab, n).astype(np.int32), m)
            for n, m in ((6, 5), (3, 9), (8, 4))]

    def serve(which):
        eng = ServeEngine(model, params, n_slots=2, max_seq=128)
        for rid in which:
            assert eng.submit(Request(rid, reqs[rid][0],
                                      max_new_tokens=reqs[rid][1]))
        rep = eng.run()
        assert rep.mode == "token"
        return rep, eng

    together, eng = serve([0, 1, 2])
    assert together.inflight_admissions > 0
    for rid in range(3):
        alone, _ = serve([rid])
        assert alone[rid] == together[rid]
    # the tally counts routed pairs of every step, every slot, every
    # MoE layer: at most top_k per slot-step and layer, and never lost
    # across arenas
    steps = together.steps * 2 * (model.cfg.n_layers - 1)
    assert 0 < eng.tallies()["moe_routed"] <= steps * model.cfg.moe.top_k


def test_decode_through_the_kernel_matches_the_reference_route(monkeypatch):
    """Prefill, then decode steps with per-slot starts: the latent kernel's
    route (interpret mode) gives the reference route's logits and arena."""
    model = build_model(_held_dsv2())
    params = model.init(jax.random.PRNGKey(4))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 12), 0,
                                model.cfg.vocab)

    def decode():
        _, cache = model.prefill(params, tokens[:, :9],
                                 model.init_cache(2, 128))
        cache["start"] = jnp.asarray([0, 4], jnp.int32)
        out = []
        for t in range(9, 12):
            logits, cache = model.decode_step(params, cache,
                                              tokens[:, t:t + 1])
            out.append(logits)
        return jnp.stack(out), cache

    want_logits, want_cache = decode()
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    before = ops.mla_decode_attention_routes.get("pallas", 0)
    got_logits, got_cache = decode()
    assert ops.mla_decode_attention_routes.get("pallas", 0) > before
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_cache["mla"]["latent"]),
                               np.asarray(want_cache["mla"]["latent"]),
                               atol=1e-5, rtol=1e-5)
