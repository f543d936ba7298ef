"""Generic decoder LM covering the assigned architecture families.

One scanned layer body per family keeps HLO size O(1) in depth:

  * plain/dense (h2o-danube, codeqwen, stablelm, gemma2): GQA attention with
    per-layer windows *as data* + gated MLP;
  * mamba+attn (hymba): parallel attention and SSM heads per layer;
  * rwkv (rwkv6): time-mix + channel-mix;
  * moe (deepseek-moe, deepseek-v2): dense-FFN prefix layers outside the
    scan, MoE layers scanned; deepseek-v2 additionally swaps GQA for MLA,
    whose decode reads one latent arena of every layer in place;
  * vision (llama-3.2-vision): period-grouped scan — each group is one
    gated cross-attention block + (period-1) self-attention layers.

Public surface: init / forward / loss / init_cache / prefill / decode_step.
"""

from __future__ import annotations

import dataclasses
from typing import Any

from jax.ad_checkpoint import checkpoint_name

import jax
import jax.numpy as jnp
from jax import lax

from . import attention as A
from . import mamba as M
from . import mla as ML
from . import moe as MO
from . import rwkv as R
from .common import (KeyGen, apply_mlp, apply_norm, chunked_ce_loss,
                     constrain_batch, dt, embed_init, init_mlp, init_norm,
                     dense_init, softcap)
from .config import ArchConfig, FULL_WINDOW

Params = dict
Cache = dict


@dataclasses.dataclass(frozen=True)
class DecoderLM:
    cfg: ArchConfig
    remat: bool = False     # activation-checkpoint each scanned layer
    # keep every decoded token's experts in the decode cache
    # (``moe_picks``, (MoE layers, B, S, top_k)), for comparing routing
    record_routing: bool = False

    def _maybe_remat(self, body):
        if not self.remat:
            return body
        # Save the (cheap, bf16) post-collective block outputs so the
        # backward pass does not re-run the forward's TP all-reduces /
        # all-gathers — collective traffic is the scarce resource, HBM for
        # two (B,S,D) residuals per layer is not (EXPERIMENTS.md §Perf).
        policy = jax.checkpoint_policies.save_only_these_names(
            "mixer_out", "ffn_out")
        return jax.checkpoint(body, policy=policy)

    # ------------------------------------------------------------ init ----

    def init(self, rng) -> Params:
        cfg = self.cfg
        keys = KeyGen(rng)
        dtype = dt(cfg)
        p: Params = {
            "embed": embed_init(keys(), (cfg.padded_vocab, cfg.d_model),
                                dtype),
            "final_norm": init_norm(cfg, cfg.d_model),
        }
        if not cfg.tie_embeddings:
            p["head"] = dense_init(keys(), (cfg.d_model, cfg.padded_vocab),
                                   dtype)
        if cfg.pos == "learned":
            p["pos_embed"] = embed_init(keys(), (cfg.max_seq, cfg.d_model),
                                        dtype)
        if cfg.cross_attn_period:
            p.update(self._init_vision(keys))
        elif cfg.mixer == "rwkv":
            p["layers"] = self._init_rwkv_stack(keys, cfg.n_layers)
        elif cfg.moe is not None:
            n_dense = len(cfg.dense_layers)
            assert cfg.dense_layers == tuple(range(n_dense)), \
                "dense MoE layers must be a prefix"
            p["dense_prefix"] = [
                self._init_block(keys, moe=False) for _ in range(n_dense)]
            p["layers"] = self._init_block(
                keys, moe=True, stack=(cfg.n_layers - n_dense,))
        else:
            p["layers"] = self._init_block(keys, moe=False,
                                           stack=(cfg.n_layers,))
        return p

    def _init_block(self, keys: KeyGen, moe: bool,
                    stack: tuple[int, ...] = ()) -> dict:
        cfg = self.cfg
        blk: dict = {"ln1": self._norm_stack(stack),
                     "ln2": self._norm_stack(stack)}
        if cfg.post_norm:
            blk["post_ln1"] = self._norm_stack(stack)
            blk["post_ln2"] = self._norm_stack(stack)
        if cfg.mla is not None:
            blk["mla"] = ML.init_mla(keys, cfg, stack)
        else:
            blk["attn"] = A.init_attn(keys, cfg, stack)
        if cfg.mixer == "mamba+attn":
            blk["mamba"] = M.init_mamba(keys, cfg, stack)
        if moe:
            blk["moe"] = MO.init_moe(keys, cfg, stack)
        else:
            blk["mlp"] = init_mlp(keys, cfg, cfg.d_model, cfg.d_ff, stack)
        return blk

    def _norm_stack(self, stack: tuple[int, ...]) -> dict:
        cfg = self.cfg
        p = {"scale": jnp.ones((*stack, cfg.d_model), jnp.float32)}
        if cfg.norm == "ln":
            p["bias"] = jnp.zeros((*stack, cfg.d_model), jnp.float32)
        return p

    def _init_rwkv_stack(self, keys: KeyGen, n: int) -> dict:
        cfg = self.cfg
        blk = {"ln1": self._norm_stack((n,)), "ln2": self._norm_stack((n,))}
        blk["rwkv"] = R.init_rwkv(keys, cfg, (n,))
        return blk

    def _init_vision(self, keys: KeyGen) -> dict:
        cfg = self.cfg
        period = cfg.cross_attn_period
        groups = cfg.n_layers // period
        n_self = period - 1
        return {
            "cross": {
                "ln": self._norm_stack((groups,)),
                "attn": A.init_cross_attn(keys, cfg, (groups,)),
                "ln2": self._norm_stack((groups,)),
                "mlp": init_mlp(keys, cfg, cfg.d_model, cfg.d_ff, (groups,)),
            },
            "layers": self._init_block(keys, moe=False,
                                       stack=(groups, n_self)),
        }

    # --------------------------------------------------------- forward ----

    def _windows(self) -> jax.Array:
        return jnp.asarray(self.cfg.layer_windows, jnp.int32)

    def _embed(self, p: Params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        x = p["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
        if cfg.pos == "learned":
            S = tokens.shape[1]
            x = x + p["pos_embed"][:S].astype(x.dtype)
        return constrain_batch(x)

    def _head(self, p: Params, x: jax.Array) -> jax.Array:
        cfg = self.cfg
        head = p["embed"].T if cfg.tie_embeddings else p["head"]
        logits = jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))
        logits = softcap(logits.astype(jnp.float32), cfg.final_softcap)
        if cfg.padded_vocab != cfg.vocab:
            logits = jnp.where(jnp.arange(cfg.padded_vocab) < cfg.vocab,
                               logits, -1e30)
        return logits

    def _block_fwd(self, blk: dict, x: jax.Array, window) -> tuple:
        """One (possibly scanned) decoder block. Returns (x, aux)."""
        cfg = self.cfg
        h = apply_norm(cfg, blk["ln1"], x)
        if cfg.mla is not None:
            mix = ML.mla_forward(cfg, blk["mla"], h)
        else:
            mix = A.attn_forward(cfg, blk["attn"], h, window=window)
        if cfg.mixer == "mamba+attn":
            mix = mix + M.mamba_forward(cfg, blk["mamba"], h)
        if cfg.post_norm:
            mix = apply_norm(cfg, blk["post_ln1"], mix)
        mix = checkpoint_name(mix, "mixer_out")
        x = x + mix
        h = apply_norm(cfg, blk["ln2"], x)
        if "moe" in blk:
            y, aux = MO.moe_ffn(cfg, blk["moe"], h)
        else:
            y = apply_mlp(cfg, blk["mlp"], h)
            aux = {"moe_load_balance": jnp.zeros((), jnp.float32),
                   "moe_z_loss": jnp.zeros((), jnp.float32)}
        if cfg.post_norm:
            y = apply_norm(cfg, blk["post_ln2"], y)
        y = checkpoint_name(y, "ffn_out")
        return x + y, aux

    def forward(self, p: Params, tokens: jax.Array,
                img: jax.Array | None = None) -> tuple[jax.Array, dict]:
        """Full-sequence forward to final hidden states (B, S, D)."""
        cfg = self.cfg
        x = self._embed(p, tokens)
        zero_aux = {"moe_load_balance": jnp.zeros((), jnp.float32),
                    "moe_z_loss": jnp.zeros((), jnp.float32)}

        if cfg.cross_attn_period:
            x = self._vision_fwd(p, x, img)
            aux = zero_aux
        elif cfg.mixer == "rwkv":
            def body(xc, blk):
                h = apply_norm(cfg, blk["ln1"], xc)
                xc = xc + R.rwkv_time_mix(cfg, blk["rwkv"], h)
                h = apply_norm(cfg, blk["ln2"], xc)
                xc = xc + R.rwkv_channel_mix(cfg, blk["rwkv"], h)
                return xc, None
            x, _ = lax.scan(self._maybe_remat(body), x, p["layers"])
            aux = zero_aux
        else:
            aux_tot = zero_aux
            windows = self._windows()
            n_dense = len(cfg.dense_layers) if cfg.moe is not None else 0
            for i in range(n_dense):
                x, _ = self._block_fwd(p["dense_prefix"][i], x,
                                       int(cfg.layer_windows[i]))

            def body(xc, inp):
                blk, win = inp
                xc, aux_l = self._block_fwd(blk, xc, win)
                return xc, aux_l

            x, auxs = lax.scan(self._maybe_remat(body), x,
                               (p["layers"], windows[n_dense:]))
            aux = {k: auxs[k].sum() for k in aux_tot}
        return apply_norm(cfg, p["final_norm"], x), aux

    def _vision_fwd(self, p: Params, x: jax.Array,
                    img: jax.Array) -> jax.Array:
        cfg = self.cfg
        if img is None:
            raise ValueError(f"{cfg.name} needs image embeddings")
        img = img.astype(x.dtype)

        def group(xc, inp):
            cross, selfs = inp
            # gated cross-attention block
            h = apply_norm(cfg, cross["ln"], xc)
            k, v = A.cross_kv(cfg, cross["attn"], img)
            xc = xc + A.cross_attn_forward(cfg, cross["attn"], h, k, v)
            h = apply_norm(cfg, cross["ln2"], xc)
            xc = xc + apply_mlp(cfg, cross["mlp"], h) \
                * jnp.tanh(cross["attn"]["gate"]).astype(xc.dtype)

            def self_layer(xi, blk):
                xi, _ = self._block_fwd(blk, xi, FULL_WINDOW)
                return xi, None

            xc, _ = lax.scan(self_layer, xc, selfs)
            return xc, None

        x, _ = lax.scan(self._maybe_remat(group), x,
                        (p["cross"], p["layers"]))
        return x

    # ------------------------------------------------------------ loss ----

    def loss(self, p: Params, batch: dict) -> tuple[jax.Array, dict]:
        cfg = self.cfg
        x, aux = self.forward(p, batch["tokens"], img=batch.get("img"))
        head = p["embed"].T if cfg.tie_embeddings else p["head"]
        nll, w = chunked_ce_loss(x, head, batch["labels"],
                                 batch.get("mask"),
                                 final_softcap=cfg.final_softcap,
                                 valid_vocab=cfg.vocab)
        ce = nll / jnp.maximum(w, 1.0)
        total = ce
        metrics = {"ce": ce, "tokens": w}
        if cfg.moe is not None:
            total = total + cfg.moe.router_aux_weight * aux["moe_load_balance"]
            total = total + cfg.moe.router_z_weight * aux["moe_z_loss"]
            metrics.update(aux)
        metrics["loss"] = total
        return total, metrics

    # ---------------------------------------------------------- decode ----

    def init_cache(self, batch: int, max_seq: int) -> Cache:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.compute_dtype)
        cache: Cache = {"pos": jnp.zeros((), jnp.int32)}
        if cfg.mixer == "rwkv":
            cache["rwkv"] = R.init_rwkv_cache(cfg, cfg.n_layers, batch, dtype)
            return cache
        n_dense = len(cfg.dense_layers) if cfg.moe is not None else 0
        n_scan = cfg.n_layers - n_dense
        if cfg.cross_attn_period:
            period = cfg.cross_attn_period
            groups = cfg.n_layers // period
            cache["kv"] = A.init_kv_cache(cfg, groups * (period - 1), batch,
                                          max_seq, dtype)
            cache["cross_kv"] = {
                "k": jnp.zeros((groups, batch, cfg.n_kv_heads,
                                cfg.n_img_tokens, cfg.d_head), dtype),
                "v": jnp.zeros((groups, batch, cfg.n_kv_heads,
                                cfg.n_img_tokens, cfg.d_head), dtype)}
            return cache
        if cfg.moe is not None:
            cache["moe_routed"] = jnp.zeros((), jnp.int32)
            if self.record_routing:
                cache["moe_picks"] = jnp.zeros(
                    (n_scan, batch, max_seq, cfg.moe.top_k), jnp.int32)
        if cfg.mla is not None:
            cache["mla"] = ML.init_mla_cache(cfg, cfg.n_layers, batch,
                                             max_seq, dtype)
        else:
            cache["kv"] = A.init_kv_cache(cfg, n_scan, batch, max_seq, dtype)
            if n_dense:
                cache["kv_dense"] = A.init_kv_cache(cfg, n_dense, batch,
                                                    max_seq, dtype)
        if cfg.mixer == "mamba+attn":
            cache["mamba"] = M.init_mamba_cache(cfg, cfg.n_layers, batch,
                                                dtype)
        return cache

    def _block_decode(self, blk: dict, x, window, pos, kv=None, layer=None,
                      mla=None, mamba=None, start=None):
        """One-layer decode. ``kv`` is the whole (k, v) arena, ``mla`` the
        whole latent arena, and ``layer`` this layer's index in it; the
        new K/V or latent row comes back for the caller to write once
        every layer has run. Returns (x, new_kv, new_mla, new_mamba,
        moe_aux), the last the expert layer's aux (None without
        experts). ``start`` (per-slot attention-window
        origins, token-level serving) only reaches the attention paths —
        the :attr:`decode_supports_start` gate keeps it None elsewhere."""
        cfg = self.cfg
        h = apply_norm(cfg, blk["ln1"], x)
        new_kv = new_mla = new_mamba = None
        if cfg.mla is not None:
            mix, new_mla = ML.mla_decode(cfg, blk["mla"], h, mla, layer, pos,
                                         start=start)
        else:
            mix, k, v = A.attn_decode(cfg, blk["attn"], h, kv[0], kv[1],
                                      layer, pos, window=window, start=start)
            new_kv = (k, v)
        if cfg.mixer == "mamba+attn":
            mo, ssm, win = M.mamba_decode(cfg, blk["mamba"], h, mamba[0],
                                          mamba[1])
            mix = mix + mo
            new_mamba = (ssm, win)
        if cfg.post_norm:
            mix = apply_norm(cfg, blk["post_ln1"], mix)
        x = x + mix
        h = apply_norm(cfg, blk["ln2"], x)
        aux = None
        if "moe" in blk:
            y, aux = MO.moe_ffn(cfg, blk["moe"], h)
        else:
            y = apply_mlp(cfg, blk["mlp"], h)
        if cfg.post_norm:
            y = apply_norm(cfg, blk["post_ln2"], y)
        return x + y, new_kv, new_mla, new_mamba, aux

    @property
    def decode_supports_start(self) -> bool:
        """Whether :meth:`decode_step` honors a per-slot ``cache["start"]``
        vector (token-level continuous batching, ``repro.serve``). True
        for rotary/positionless attention stacks, GQA or MLA (both
        arenas are start-masked): recurrent mixers (rwkv, mamba+attn)
        carry state that a mask cannot scope to one slot's window,
        cross-attention caches are not start-masked, and learned
        positional embeddings index absolute arena positions.
        ``ServeEngine(mode="auto")`` reads this to pick token-level vs
        cohort scheduling."""
        cfg = self.cfg
        return (cfg.mixer == "attn" and not cfg.cross_attn_period
                and cfg.pos != "learned")

    @property
    def cache_tallies(self) -> tuple[str, ...]:
        """Scalar int32 entries of the decode cache that count work on the
        device, step after step (``moe_routed``: (token, k) pairs the
        held experts computed); ``ServeEngine.tallies`` sums them."""
        return ("moe_routed",) if self.cfg.moe is not None else ()

    def decode_step(self, p: Params, cache: Cache, tokens: jax.Array
                    ) -> tuple[jax.Array, Cache]:
        """tokens: (B, 1) -> (logits (B, 1, V), new cache). An optional
        ``cache["start"]`` (B,) vector scopes each batch row's attention
        to cache positions [start[b], pos] — see
        :attr:`decode_supports_start`."""
        cfg = self.cfg
        pos = cache["pos"]
        x = self._embed_at(p, tokens, pos)
        cache = dict(cache)

        if cfg.mixer == "rwkv":
            x, cache["rwkv"] = self._rwkv_decode(p, x, cache["rwkv"])
        elif cfg.cross_attn_period:
            x, cache = self._vision_decode(p, x, cache, pos)
        else:
            x, cache = self._stack_decode(p, x, cache, pos,
                                          start=cache.get("start"))
        x = apply_norm(cfg, p["final_norm"], x)
        logits = self._head(p, x)
        cache["pos"] = pos + 1
        return logits, cache

    def _embed_at(self, p: Params, tokens, pos):
        cfg = self.cfg
        x = p["embed"][tokens].astype(jnp.dtype(cfg.compute_dtype))
        if cfg.pos == "learned":
            pe = lax.dynamic_slice_in_dim(p["pos_embed"], pos,
                                          tokens.shape[1], axis=0)
            x = x + pe.astype(x.dtype)
        return x

    def _rwkv_decode(self, p, x, rc):
        cfg = self.cfg

        def body(xc, inp):
            blk, wkv, prev_t, prev_c = inp
            h = apply_norm(cfg, blk["ln1"], xc)
            o, wkv, prev_t = R.rwkv_time_mix_decode(cfg, blk["rwkv"], h,
                                                    wkv, prev_t)
            xc = xc + o
            h = apply_norm(cfg, blk["ln2"], xc)
            o, prev_c = R.rwkv_channel_mix_decode(cfg, blk["rwkv"], h, prev_c)
            return xc + o, (wkv, prev_t, prev_c)

        x, (wkv, pt, pc) = lax.scan(
            body, x, (p["layers"], rc["wkv"], rc["prev_t"], rc["prev_c"]))
        return x, {"wkv": wkv, "prev_t": pt, "prev_c": pc}

    def _stack_decode(self, p, x, cache, pos, start=None):
        cfg = self.cfg
        windows = self._windows()
        n_dense = len(cfg.dense_layers) if cfg.moe is not None else 0
        use_mla = cfg.mla is not None
        # the latent arena holds every layer, dense prefix first; the KV
        # path keeps the prefix in an arena of its own
        mla = cache["mla"]["latent"] if use_mla else None

        new_dense = []
        kd = None
        if n_dense and not use_mla:
            kd = (cache["kv_dense"]["k"], cache["kv_dense"]["v"])
        for i in range(n_dense):
            x, nkv, nm, _, _ = self._block_decode(
                p["dense_prefix"][i], x, int(cfg.layer_windows[i]), pos,
                kv=kd, layer=i, mla=mla, start=start)
            new_dense.append(nm if use_mla else nkv)
        if new_dense and not use_mla:
            cache["kv_dense"] = A.write_kv(
                cache["kv_dense"], *(jnp.stack(t) for t in zip(*new_dense)),
                pos)

        has_mamba = cfg.mixer == "mamba+attn"
        kv = None if use_mla else (cache["kv"]["k"], cache["kv"]["v"])

        def body(xc, inp):
            blk, win, layer, mamba_l = inp
            xc, nkv, nmla, nmb, aux = self._block_decode(
                blk, xc, win, pos, kv=kv, layer=layer, mla=mla,
                mamba=mamba_l, start=start)
            return xc, (nkv, nmla, nmb, aux)

        mamba_xs = (cache["mamba"]["ssm"], cache["mamba"]["conv"]) \
            if has_mamba else None

        layers = jnp.arange(cfg.n_layers - n_dense)
        if use_mla:
            layers = layers + n_dense
        xs = (p["layers"], windows[n_dense:], layers, mamba_xs)
        x, (nkv, nmla, nmb, aux) = lax.scan(body, x, xs)
        if use_mla:
            rows = jnp.concatenate([jnp.stack(new_dense), nmla]) \
                if new_dense else nmla
            cache["mla"] = {"latent": ML.write_latents(mla, rows, pos)}
        else:
            cache["kv"] = A.write_kv(cache["kv"], *nkv, pos)
        if has_mamba:
            cache["mamba"] = {"ssm": nmb[0], "conv": nmb[1]}
        if aux is not None:
            cache["moe_routed"] = cache["moe_routed"] \
                + aux["moe_routed"].sum()
            if "moe_picks" in cache:
                cache["moe_picks"] = lax.dynamic_update_slice(
                    cache["moe_picks"], aux["moe_picks"],
                    (0, 0, jnp.asarray(pos, jnp.int32), 0))
        return x, cache

    def _vision_decode(self, p, x, cache, pos):
        cfg = self.cfg
        n_self = cfg.cross_attn_period - 1
        kv = cache["kv"]
        groups = kv["k"].shape[0] // n_self

        def group(xc, inp):
            cross, selfs, ck, cv, g = inp
            h = apply_norm(cfg, cross["ln"], xc)
            xc = xc + A.cross_attn_forward(cfg, cross["attn"], h, ck, cv)
            h = apply_norm(cfg, cross["ln2"], xc)
            xc = xc + apply_mlp(cfg, cross["mlp"], h) \
                * jnp.tanh(cross["attn"]["gate"]).astype(xc.dtype)

            def self_layer(xi, inp2):
                blk, i = inp2
                xi, nkv, _, _, _ = self._block_decode(
                    blk, xi, FULL_WINDOW, pos, kv=(kv["k"], kv["v"]),
                    layer=g * n_self + i)
                return xi, nkv

            return lax.scan(self_layer, xc, (selfs, jnp.arange(n_self)))

        x, (nk, nv) = lax.scan(
            group, x, (p["cross"], p["layers"], cache["cross_kv"]["k"],
                       cache["cross_kv"]["v"], jnp.arange(groups)))
        flat = (groups * n_self, *nk.shape[2:])
        cache["kv"] = A.write_kv(kv, nk.reshape(flat), nv.reshape(flat), pos)
        return x, cache

    # --------------------------------------------------------- prefill ----

    def prefill(self, p: Params, tokens: jax.Array, cache: Cache,
                img: jax.Array | None = None) -> tuple[jax.Array, Cache]:
        """Parallel prefill: full-sequence forward with cache writes.
        Returns (last-position logits (B, 1, V), filled cache)."""
        cfg = self.cfg
        B, S = tokens.shape
        x = self._embed(p, tokens)
        cache = dict(cache)

        if cfg.mixer == "rwkv":
            def body(xc, blk):
                h = apply_norm(cfg, blk["ln1"], xc)
                o, wkv, pt = R.rwkv_time_mix_prefill(cfg, blk["rwkv"], h)
                xc = xc + o
                h = apply_norm(cfg, blk["ln2"], xc)
                o, pc = R.rwkv_channel_mix_prefill(cfg, blk["rwkv"], h)
                return xc + o, (wkv, pt.astype(x.dtype), pc.astype(x.dtype))
            x, (wkv, pt, pc) = lax.scan(body, x, p["layers"])
            cache["rwkv"] = {"wkv": wkv, "prev_t": pt, "prev_c": pc}
        elif cfg.cross_attn_period:
            x, cache = self._vision_prefill(p, x, cache, img)
        else:
            x, cache = self._stack_prefill(p, x, cache)
        cache["pos"] = cache["pos"] + S
        x = apply_norm(cfg, p["final_norm"], x)
        logits = self._head(p, x[:, -1:])
        return logits, cache

    def _block_prefill(self, blk: dict, x, window, kv=None,
                       mamba_on: bool = False):
        cfg = self.cfg
        h = apply_norm(cfg, blk["ln1"], x)
        new_kv = new_mla = new_mamba = None
        if cfg.mla is not None:
            mix, new_mla = ML.mla_prefill(cfg, blk["mla"], h)
        else:
            mix, ck, cv = A.attn_prefill(cfg, blk["attn"], h, kv[0], kv[1],
                                         window=window)
            new_kv = (ck, cv)
        if mamba_on:
            mo, ssm, win = M.mamba_prefill(cfg, blk["mamba"], h)
            mix = mix + mo
            new_mamba = (ssm, win.astype(h.dtype))
        if cfg.post_norm:
            mix = apply_norm(cfg, blk["post_ln1"], mix)
        x = x + mix
        h = apply_norm(cfg, blk["ln2"], x)
        if "moe" in blk:
            y, _ = MO.moe_ffn(cfg, blk["moe"], h)
        else:
            y = apply_mlp(cfg, blk["mlp"], h)
        if cfg.post_norm:
            y = apply_norm(cfg, blk["post_ln2"], y)
        return x + y, new_kv, new_mla, new_mamba

    def _stack_prefill(self, p, x, cache):
        cfg = self.cfg
        windows = self._windows()
        n_dense = len(cfg.dense_layers) if cfg.moe is not None else 0
        use_mla = cfg.mla is not None
        has_mamba = cfg.mixer == "mamba+attn"

        dense_rows = []
        for i in range(n_dense):
            blk = p["dense_prefix"][i]
            if use_mla:
                x, _, nm, _ = self._block_prefill(
                    blk, x, int(cfg.layer_windows[i]))
                dense_rows.append(nm)
            else:
                kd = cache["kv_dense"]
                x, nk, _, _ = self._block_prefill(
                    blk, x, int(cfg.layer_windows[i]),
                    kv=(kd["k"][i], kd["v"][i]))
                cache["kv_dense"] = {"k": kd["k"].at[i].set(nk[0]),
                                     "v": kd["v"].at[i].set(nk[1])}

        def body(xc, inp):
            blk, win, kv_l = inp
            xc, nkv, nmla, nmb = self._block_prefill(
                blk, xc, win, kv=kv_l, mamba_on=has_mamba)
            return xc, (nkv, nmla, nmb)

        kv_xs = None if use_mla else (cache["kv"]["k"], cache["kv"]["v"])
        x, (nkv, nmla, nmb) = lax.scan(
            body, x, (p["layers"], windows[n_dense:], kv_xs))
        if use_mla:
            # latent rows of every layer (L, B, S, width) at [0, S)
            rows = jnp.concatenate([jnp.stack(dense_rows), nmla]) \
                if dense_rows else nmla
            arena = cache["mla"]["latent"]
            cache["mla"] = {"latent": lax.dynamic_update_slice(
                arena, rows.astype(arena.dtype), (0, 0, 0, 0))}
        else:
            cache["kv"] = {"k": nkv[0], "v": nkv[1]}
        if has_mamba:
            cache["mamba"] = {"ssm": nmb[0], "conv": nmb[1]}
        return x, cache

    def _vision_prefill(self, p, x, cache, img):
        cfg = self.cfg
        if img is None:
            raise ValueError(f"{cfg.name} needs image embeddings")
        img = img.astype(x.dtype)
        period = cfg.cross_attn_period
        n_self = period - 1
        kv = cache["kv"]
        kshape = kv["k"].shape
        groups = kshape[0] // n_self
        k_g = kv["k"].reshape(groups, n_self, *kshape[1:])
        v_g = kv["v"].reshape(groups, n_self, *kshape[1:])

        def group(xc, inp):
            cross, selfs, kg, vg = inp
            h = apply_norm(cfg, cross["ln"], xc)
            ck, cv = A.cross_kv(cfg, cross["attn"], img)
            xc = xc + A.cross_attn_forward(cfg, cross["attn"], h, ck, cv)
            h = apply_norm(cfg, cross["ln2"], xc)
            xc = xc + apply_mlp(cfg, cross["mlp"], h) \
                * jnp.tanh(cross["attn"]["gate"]).astype(xc.dtype)

            def self_layer(xi, inp2):
                blk, kl, vl = inp2
                xi, nkv, _, _ = self._block_prefill(blk, xi, FULL_WINDOW,
                                                    kv=(kl, vl))
                return xi, nkv

            xc, (nk, nv) = lax.scan(self_layer, xc, (selfs, kg, vg))
            return xc, (nk, nv, ck, cv)

        x, (nk, nv, ck, cv) = lax.scan(group, x,
                                       (p["cross"], p["layers"], k_g, v_g))
        cache["kv"] = {"k": nk.reshape(kshape), "v": nv.reshape(kshape)}
        cache["cross_kv"] = {"k": ck, "v": cv}
        return x, cache
