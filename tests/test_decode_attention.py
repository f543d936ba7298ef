"""Decode attention over the KV arena in place: the Pallas kernel (interpret
mode) against the jnp reference on the arena's (L, B, Hkv, D, S) layout, the
route tally, and serving through the kernel route against the reference
route, with the cache donated every step."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, list_archs
from repro.core.builder import args_meta
from repro.kernels import decode_attention as da, ops, ref
from repro.models import attention as A, build_model
from repro.serve import Request, ServeEngine

L, B, D, S = 2, 4, 16, 512
TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2e-2}


def _inputs(rng, hq, hkv, dtype):
    def arr(*shape):
        return jnp.asarray(rng.standard_normal(shape), dtype)
    return (arr(L, B, hkv, D, S), arr(L, B, hkv, D, S), arr(B, hq, 1, D),
            arr(B, hkv, D), arr(B, hkv, D))


def _reference(ka, va, q, kn, vn, layer, pos, window, softcap, start):
    """``ref.decode_attention_ref`` on one layer's cache holding the
    step's K/V at ``pos``, as the arena holds it once the step is done."""
    ck = ka[layer].at[..., pos].set(kn)
    cv = va[layer].at[..., pos].set(vn)
    return ref.decode_attention_ref(q, ck, cv, pos, window, softcap,
                                    start=jnp.asarray(start, jnp.int32))


# (pos, per-slot start): pos 0; pos at a block edge; pos at max_seq - 1;
# slot starts mid-block, at a block edge, a slot whose whole live range is
# one block, and a slot admitted at pos (only the step's own token)
CASES = {
    "pos0": (0, [0, 0, 0, 0]),
    "block_edge": (256, [0, 3, 128, 256]),
    "last": (S - 1, [0, 130, 400, 500]),
    "one_block": (300, [260, 290, 299, 300]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_reference(rng, case, hq, hkv, dtype):
    pos, start = CASES[case]
    ka, va, q, kn, vn = _inputs(rng, hq, hkv, dtype)
    ints = jnp.asarray([1, pos, 0], jnp.int32)
    args = (ka, va, q, kn, vn, ints, jnp.asarray(start, jnp.int32),
            jnp.zeros((1,), jnp.float32))
    want = _reference(ka, va, q, kn, vn, 1, pos, 0, None, start)
    for config in ({"block_h": 1, "block_k": 128},
                   da.builder.default_config()):
        got = da.builder.make(config, args_meta(*args), interpret=True)(*args)
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window,softcap", [(100, None), (0, 5.0),
                                            (130, 5.0)])
def test_kernel_window_and_softcap(rng, monkeypatch, window, softcap):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    ka, va, q, kn, vn = _inputs(rng, 8, 2, jnp.float32)
    pos, start = 400, [0, 250, 330, 399]
    got = ops.decode_attention(q * 8, ka, va, kn, vn, 0, pos, window=window,
                               softcap=softcap,
                               start=jnp.asarray(start, jnp.int32))
    want = _reference(ka, va, q * 8, kn, vn, 0, pos, window, softcap, start)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_route_tally(rng, monkeypatch):
    ka, va, q, kn, vn = _inputs(rng, 4, 4, jnp.float32)

    def route(arena_k, arena_v):
        before = dict(ops.decode_attention_routes)
        ops.decode_attention(q, arena_k, arena_v, kn, vn, 0, 5)
        return {k: v - before.get(k, 0)
                for k, v in ops.decode_attention_routes.items()
                if v != before.get(k, 0)}

    assert route(ka, va) == {"reference:backend": 1}
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    assert route(ka, va) == {"pallas": 1}
    assert route(ka[..., :100], va[..., :100]) == {"reference:shape": 1}


@pytest.mark.parametrize("backend", ["reference", "interpret"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("pos", [0, 127, 300, S - 1])
def test_write_kv_writes_one_position_of_every_layer(rng, monkeypatch,
                                                     backend, dtype, pos):
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", backend)
    ka, va, _, _, _ = _inputs(rng, 4, 4, dtype)
    k = jnp.asarray(rng.standard_normal((L, B, 4, D)), dtype)
    out = A.write_kv({"k": ka, "v": va}, k, -k, pos)
    np.testing.assert_array_equal(out["k"], ka.at[..., pos].set(k))
    np.testing.assert_array_equal(out["v"], va.at[..., pos].set(-k))


def _serve(model, params, mode: str, prompts) -> dict:
    eng = ServeEngine(model, params, n_slots=2, max_seq=128, mode=mode)
    for rid, (prompt, n_new) in enumerate(prompts):
        assert eng.submit(Request(rid, prompt, max_new_tokens=n_new))
    report = eng.run()
    assert report.steps == eng.steps_run
    return dict(report.outputs)


@pytest.mark.parametrize("mode", ["token", "cohort"])
def test_serving_through_the_kernel_matches_the_reference(monkeypatch, mode):
    cfg = dataclasses.replace(get_arch("stablelm-1.6b").reduced(),
                              max_seq=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    prompts = [(rng.integers(0, cfg.vocab, size=n, dtype=np.int32), m)
               for n, m in ((5, 6), (3, 9), (7, 4))]
    want = _serve(model, params, mode, prompts)
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    jax.clear_caches()      # the route is chosen when the step is traced
    before = ops.decode_attention_routes.get("pallas", 0)
    got = _serve(model, params, mode, prompts)
    assert ops.decode_attention_routes.get("pallas", 0) > before
    assert got == want
    assert all(len(got[i]) == m for i, (_, m) in enumerate(prompts))


def test_the_engine_donates_the_cache():
    """Each step consumes the cache it is given: the arena passed in is
    gone once the step returns, and the engine only reads the new one."""
    model = build_model(get_arch("stablelm-1.6b").reduced())
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, n_slots=2, max_seq=128)
    cache = model.init_cache(2, 128)
    arena = cache["kv"]["k"]
    _, new = eng._decode_once(cache, np.zeros((2, 1), np.int32),
                              np.zeros(2, np.int32))
    assert arena.is_deleted()
    assert new["kv"]["k"].shape == (model.cfg.n_layers, 2,
                                    model.cfg.n_kv_heads,
                                    model.cfg.d_head, 128)
    assert int(new["pos"]) == 1


def _kv_archs():
    return [a for a in list_archs()
            if "kv" in build_model(get_arch(a).reduced()).init_cache(1, 128)]


@pytest.mark.parametrize("arch", _kv_archs())
def test_every_arena_reader_decodes_through_the_kernel(monkeypatch, arch):
    """Prefill, then one decode step: the kernel route's logits and arena
    equal the reference route's for every stack that keeps a KV arena
    (GQA, windows, softcaps, MoE dense prefixes, hybrid, vision and
    encoder-decoder)."""
    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 10), 0, cfg.vocab)
    extra = ()
    if cfg.enc_dec:
        extra = (jax.random.normal(jax.random.PRNGKey(6),
                                   (2, cfg.enc_seq, cfg.d_model)),)
    elif cfg.cross_attn_period:
        extra = (jax.random.normal(jax.random.PRNGKey(6),
                                   (2, cfg.n_img_tokens, cfg.d_model)),)

    def decode():
        _, cache = model.prefill(params, tokens[:, :9],
                                 model.init_cache(2, 128), *extra)
        return model.decode_step(params, cache, tokens[:, 9:])

    want_logits, want_cache = decode()
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "interpret")
    before = ops.decode_attention_routes.get("pallas", 0)
    got_logits, got_cache = decode()
    assert ops.decode_attention_routes.get("pallas", 0) > before
    np.testing.assert_allclose(np.asarray(got_logits),
                               np.asarray(want_logits), atol=1e-4, rtol=1e-4)
    for name in ("k", "v"):
        np.testing.assert_allclose(np.asarray(got_cache["kv"][name]),
                                   np.asarray(want_cache["kv"][name]),
                                   atol=1e-5, rtol=1e-5)
