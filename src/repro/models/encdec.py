"""Encoder-decoder backbone (whisper-base). The conv/mel frontend is a stub
per the assignment: ``input_specs`` feeds precomputed frame embeddings
(B, enc_seq, d_model) straight into the encoder. Encoder = non-causal
self-attention stack; decoder = causal self-attention + cross-attention to
the encoder output. Cross K/V are computed once at prefill and cached.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from . import attention as A
from .common import (KeyGen, apply_mlp, apply_norm, chunked_ce_loss, dt,
                     embed_init, init_mlp, softcap, dense_init)
from .config import ArchConfig, FULL_WINDOW

Params = dict
Cache = dict


@dataclasses.dataclass(frozen=True)
class EncDecLM:
    cfg: ArchConfig
    remat: bool = False

    def _maybe_remat(self, body):
        import jax as _jax
        return _jax.checkpoint(body) if self.remat else body

    # ------------------------------------------------------------ init ----

    def _norm_stack(self, stack: tuple[int, ...]) -> dict:
        cfg = self.cfg
        p = {"scale": jnp.ones((*stack, cfg.d_model), jnp.float32)}
        if cfg.norm == "ln":
            p["bias"] = jnp.zeros((*stack, cfg.d_model), jnp.float32)
        return p

    def init(self, rng) -> Params:
        cfg = self.cfg
        keys = KeyGen(rng)
        dtype = dt(cfg)
        ne, nd = cfg.n_enc_layers, cfg.n_layers
        p: Params = {
            "embed": embed_init(keys(), (cfg.padded_vocab, cfg.d_model),
                                dtype),
            "dec_pos": embed_init(keys(), (cfg.max_seq, cfg.d_model), dtype),
            "enc_pos": embed_init(keys(), (cfg.enc_seq, cfg.d_model), dtype),
            "final_norm": self._norm_stack(()),
            "enc_final_norm": self._norm_stack(()),
            "encoder": {
                "ln1": self._norm_stack((ne,)),
                "attn": A.init_attn(keys, cfg, (ne,)),
                "ln2": self._norm_stack((ne,)),
                "mlp": init_mlp(keys, cfg, cfg.d_model, cfg.d_ff, (ne,)),
            },
            "decoder": {
                "ln1": self._norm_stack((nd,)),
                "attn": A.init_attn(keys, cfg, (nd,)),
                "ln_x": self._norm_stack((nd,)),
                "cross": A.init_cross_attn(keys, cfg, (nd,)),
                "ln2": self._norm_stack((nd,)),
                "mlp": init_mlp(keys, cfg, cfg.d_model, cfg.d_ff, (nd,)),
            },
        }
        if not cfg.tie_embeddings:
            p["head"] = dense_init(keys(), (cfg.d_model, cfg.padded_vocab),
                                   dtype)
        return p

    # ---------------------------------------------------------- encode ----

    def encode(self, p: Params, frames: jax.Array) -> jax.Array:
        """frames: (B, T, D) stubbed frontend embeddings -> memory."""
        cfg = self.cfg
        x = frames.astype(jnp.dtype(cfg.compute_dtype))
        x = x + p["enc_pos"][:x.shape[1]].astype(x.dtype)

        def body(xc, blk):
            h = apply_norm(cfg, blk["ln1"], xc)
            xc = xc + A.attn_forward(cfg, blk["attn"], h, causal=False)
            h = apply_norm(cfg, blk["ln2"], xc)
            return xc + apply_mlp(cfg, blk["mlp"], h), None

        x, _ = lax.scan(self._maybe_remat(body), x, p["encoder"])
        return apply_norm(cfg, p["enc_final_norm"], x)

    # --------------------------------------------------------- forward ----

    def _dec_embed(self, p, tokens, pos0=0):
        cfg = self.cfg
        x = p["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
        S = tokens.shape[1]
        if isinstance(pos0, int) and pos0 == 0:
            pe = p["dec_pos"][:S]
        else:
            pe = lax.dynamic_slice_in_dim(p["dec_pos"], pos0, S, axis=0)
        return x + pe.astype(x.dtype)

    def _head(self, p, x):
        cfg = self.cfg
        head = p["embed"].T if cfg.tie_embeddings else p["head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
        if cfg.padded_vocab != cfg.vocab:
            logits = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab,
                               logits, -1e30)
        return logits

    def forward(self, p: Params, tokens: jax.Array,
                frames: jax.Array) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        memory = self.encode(p, frames)
        x = self._dec_embed(p, tokens)

        def body(xc, blk):
            h = apply_norm(cfg, blk["ln1"], xc)
            xc = xc + A.attn_forward(cfg, blk["attn"], h, causal=True)
            h = apply_norm(cfg, blk["ln_x"], xc)
            ck, cv = A.cross_kv(cfg, blk["cross"], memory)
            xc = xc + A.cross_attn_forward(cfg, blk["cross"], h, ck, cv,
                                           gated=False)
            h = apply_norm(cfg, blk["ln2"], xc)
            return xc + apply_mlp(cfg, blk["mlp"], h), None

        x, _ = lax.scan(self._maybe_remat(body), x, p["decoder"])
        x = apply_norm(cfg, p["final_norm"], x)
        aux = {"moe_load_balance": jnp.zeros((), jnp.float32),
               "moe_z_loss": jnp.zeros((), jnp.float32)}
        return x, aux

    def loss(self, p: Params, batch: dict) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x, _ = self.forward(p, batch["tokens"], batch["frames"])
        head = p["embed"].T if cfg.tie_embeddings else p["head"]
        nll, w = chunked_ce_loss(x, head, batch["labels"],
                                 batch.get("mask"), valid_vocab=cfg.vocab)
        ce = nll / jnp.maximum(w, 1.0)
        return ce, {"ce": ce, "loss": ce, "tokens": w}

    # ---------------------------------------------------------- decode ----

    def init_cache(self, batch: int, max_seq: int) -> Cache:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        nd = cfg.n_layers
        return {
            "pos": jnp.zeros((), jnp.int32),
            "kv": A.init_kv_cache(cfg, nd, batch, max_seq, dtype),
            "cross_kv": {
                "k": jnp.zeros((nd, batch, cfg.n_kv_heads, cfg.enc_seq,
                                cfg.d_head), dtype),
                "v": jnp.zeros((nd, batch, cfg.n_kv_heads, cfg.enc_seq,
                                cfg.d_head), dtype)},
        }

    def prefill(self, p: Params, tokens: jax.Array, cache: Cache,
                frames: jax.Array) -> tuple[jax.Array, Cache]:
        cfg = self.cfg
        memory = self.encode(p, frames)
        x = self._dec_embed(p, tokens)
        cache = dict(cache)

        def body(xc, inp):
            blk, kl, vl = inp
            h = apply_norm(cfg, blk["ln1"], xc)
            o, nk, nv = A.attn_prefill(cfg, blk["attn"], h, kl, vl)
            xc = xc + o
            h = apply_norm(cfg, blk["ln_x"], xc)
            ck, cv = A.cross_kv(cfg, blk["cross"], memory)
            xc = xc + A.cross_attn_forward(cfg, blk["cross"], h, ck, cv,
                                           gated=False)
            h = apply_norm(cfg, blk["ln2"], xc)
            return xc + apply_mlp(cfg, blk["mlp"], h), (nk, nv, ck, cv)

        kv = cache["kv"]
        x, (nk, nv, ck, cv) = lax.scan(body, x,
                                       (p["decoder"], kv["k"], kv["v"]))
        cache["kv"] = {"k": nk, "v": nv}
        cache["cross_kv"] = {"k": ck, "v": cv}
        cache["pos"] = cache["pos"] + tokens.shape[1]
        x = apply_norm(cfg, p["final_norm"], x)
        return self._head(p, x[:, -1:]), cache

    def decode_step(self, p: Params, cache: Cache, tokens: jax.Array
                    ) -> tuple[jax.Array, Cache]:
        cfg = self.cfg
        pos = cache["pos"]
        x = self._dec_embed(p, tokens, pos0=pos)
        cache = dict(cache)

        kv, xkv = cache["kv"], cache["cross_kv"]

        def body(xc, inp):
            blk, layer, ck, cv = inp
            h = apply_norm(cfg, blk["ln1"], xc)
            o, nk, nv = A.attn_decode(cfg, blk["attn"], h, kv["k"], kv["v"],
                                      layer, pos)
            xc = xc + o
            h = apply_norm(cfg, blk["ln_x"], xc)
            xc = xc + A.cross_attn_forward(cfg, blk["cross"], h, ck, cv,
                                           gated=False)
            h = apply_norm(cfg, blk["ln2"], xc)
            return xc + apply_mlp(cfg, blk["mlp"], h), (nk, nv)

        x, (nk, nv) = lax.scan(
            body, x, (p["decoder"], jnp.arange(cfg.n_layers), xkv["k"],
                      xkv["v"]))
        cache["kv"] = A.write_kv(kv, nk, nv, pos)
        cache["pos"] = pos + 1
        x = apply_norm(cfg, p["final_norm"], x)
        return self._head(p, x), cache
