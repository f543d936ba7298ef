"""The benchmark's plain references agree with the program where the
program is right: the stencil step with ``kernels/ref.py``, the decoder
with ``models/`` at a small size, both on the CPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness
from bench.reference import stablelm_ref, stencils, weights

from bench_tiny import TINY, merge


#: Agreement to rounding: XLA may contract multiply-adds differently in the
#: slab-wise reference and the whole-field oracle, which moves a float32
#: result by an ulp and can flip a bfloat16 rounding (2**-8 of the field's
#: largest value at most).
ROUNDING = {"float32": 1e-6, "bfloat16": 2.0 ** -8}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_stencil_reference_matches_the_program_oracle(small_fields, dtype):
    from repro.kernels import ref as program_ref
    u, v, w, evisc, scal = (jnp.asarray(a) for a in small_fields)
    u, v, w, evisc = (f.astype(dtype) for f in (u, v, w, evisc))
    got = stencils.advec_u(u, v, w, scal)
    want = jax.jit(program_ref.advec_u_ref)(u, v, w, scal)
    assert got.dtype == want.dtype
    assert float(stencils.rel_err(got, want)) <= ROUNDING[dtype]
    for g, r in zip(stencils.diff_uvw(u, v, w, evisc, scal),
                    jax.jit(program_ref.diff_uvw_ref)(u, v, w, evisc, scal)):
        assert float(stencils.rel_err(g, r)) <= ROUNDING[dtype]


def test_rel_err_flags_non_finite_results():
    want = jnp.ones((4, 4))
    assert float(stencils.rel_err(want.at[0, 0].set(jnp.nan), want)) \
        == float("inf")
    assert float(stencils.rel_err(want * 1.5, want)) == pytest.approx(0.5)


def tiny_cfg() -> dict:
    return merge(harness.load_json("configs", "stablelm-1.6b"),
                  TINY[("configs", "stablelm-1.6b")])


def test_one_layer_is_the_same_alone_and_stacked():
    cfg = tiny_cfg()
    root = harness.key_from_seed(2 ** 33 + 5)
    stacked = jax.jit(lambda r: weights.stacked_layers(r, cfg))(
        root)
    for l in range(cfg["num_hidden_layers"]):
        one = weights.layer(root, l, cfg)
        for k, v in one.items():
            np.testing.assert_array_equal(np.asarray(v),
                                          np.asarray(stacked[k][l]))


def test_decoder_reference_matches_the_program_forward():
    from repro.models import build_model
    cfg = tiny_cfg()
    serve = harness.load_module("drivers", "serve_batch")
    model = build_model(serve.program_arch(cfg))
    seed = 12345
    params = serve.make_params(cfg, seed, model)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg["vocab_size"], (2, 40)).astype(np.int32)
    x, _ = model.forward(params, jnp.asarray(tokens))
    logits = np.asarray(jnp.einsum("bsd,dv->bsv", x, params["head"]))
    rows = np.array([(b, s) for b in range(2) for s in range(40)], np.int32)
    top = logits.reshape(-1, logits.shape[-1]).argmax(-1).astype(np.int32)
    best, picked, first = stablelm_ref.logits_at(
        cfg, harness.key_from_seed(seed), tokens, rows, top)
    np.testing.assert_array_equal(first, top)
    np.testing.assert_allclose(best, logits.max(-1).reshape(-1), rtol=1e-4,
                               atol=1e-4)
    assert np.max(best - picked) < 1e-4


def test_served_rows_point_at_the_logit_that_produced_each_token():
    prompt = np.array([5, 6, 7], np.int32)
    tokens, rows, targets = stablelm_ref.served_rows([(prompt, [8, 9])], 8)
    assert tokens[0].tolist() == [5, 6, 7, 8, 0, 0, 0, 0]
    assert rows.tolist() == [[0, 2], [0, 3]]
    assert targets.tolist() == [8, 9]
