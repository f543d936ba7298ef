"""Fine-grained mixture-of-experts (DeepSeek-MoE / DeepSeek-V2 style):
shared experts + routed top-k experts with capacity-bucketed einsum dispatch.

Routing (:func:`route`): float32 softmax scores over all ``n_routed``
experts. With ``n_group > 1`` it is DeepSeek-V2's group-limited greedy:
each group of ``n_routed / n_group`` experts is scored by its best
expert, the ``topk_group`` best groups are kept, and the ``top_k``
experts are picked among theirs. Gates are the picked scores,
renormalised to sum to one when ``norm_topk`` (deepseek-moe), then
scaled by ``routed_scale`` (DeepSeek-V2: not renormalised, x16).

Held share: a layer holds the weights of experts ``[first, first +
count)`` (``MoECfg.held``; all by default), as one rank of an
expert-parallel deployment does. It routes every token over all
``n_routed`` experts and computes the routed part only for the (token,
k) pairs whose expert it holds; the shared experts are computed whole.
Summed over ranks whose held ranges cover all experts, the routed parts
plus one shared part are the uncut layer. On one chip nothing stands in
for the experts that other ranks hold.

The dispatch/combine one-hots lower to all-to-alls when the expert axis is
sharded over the ``model`` mesh axis (expert parallelism). The sequence is
processed in chunks (``moe.chunk``) via ``lax.scan`` so dispatch tensors stay
VMEM-sized; capacity is per-chunk, at least 4 per expert, so a one-token
decode step drops nothing. Router aux losses (load-balance + z-loss) are
returned for the train loss, with ``moe_routed``, the (token, k) pairs
the held experts computed, and ``moe_picks``, each token's experts.
:data:`expert_rows` records, at trace time, the rows the held-expert
matmuls compute per call.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .common import KeyGen, dense_init, dt
from .config import ArchConfig, MoECfg

#: Rows the held-expert matmuls compute in one layer call, by the
#: (batch, sequence) shape of the call's input; written when a call is
#: traced (held experts x capacity x batch rows x chunks).
expert_rows: dict[tuple[int, int], int] = {}


def init_moe(keys: KeyGen, cfg: ArchConfig,
             stack: tuple[int, ...] = ()) -> dict:
    e = cfg.moe
    d = cfg.d_model
    dtype = dt(cfg)
    n = e.n_held
    p = {
        "router": dense_init(keys(), (*stack, d, e.n_routed), jnp.float32),
        "w_in": dense_init(keys(), (*stack, n, d, e.d_expert),
                           dtype, in_axis=-2),
        "w_gate": dense_init(keys(), (*stack, n, d, e.d_expert),
                             dtype, in_axis=-2),
        "w_out": dense_init(keys(), (*stack, n, e.d_expert, d),
                            dtype, in_axis=-2),
    }
    if e.n_shared:
        sh = e.n_shared * e.d_expert
        p["shared_in"] = dense_init(keys(), (*stack, d, sh), dtype)
        p["shared_gate"] = dense_init(keys(), (*stack, d, sh), dtype)
        p["shared_out"] = dense_init(keys(), (*stack, sh, d), dtype)
    return p


def _capacity(e: MoECfg, chunk_tokens: int) -> int:
    cap = int(chunk_tokens * e.top_k / e.n_routed * e.capacity_factor)
    return max(4, ((cap + 3) // 4) * 4)


def route(e: MoECfg, logits: jax.Array
          ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """logits (..., n_routed) -> (gates (..., top_k), experts (..., top_k),
    scores (..., n_routed)), all but the experts float32."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    scores = probs
    if e.n_group > 1:
        groups = probs.reshape(*probs.shape[:-1], e.n_group, -1)
        _, best = lax.top_k(groups.max(-1), e.topk_group)    # (..., tg)
        kept = jax.nn.one_hot(best, e.n_group, dtype=jnp.bool_).any(-2)
        scores = jnp.where(kept[..., None], groups, 0.0).reshape(
            probs.shape)
    gate, sel = lax.top_k(scores, e.top_k)
    if e.norm_topk:
        gate = gate / (gate.sum(-1, keepdims=True) + 1e-9)
    return gate * e.routed_scale, sel, probs


def shared_ffn(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    """The shared experts' part, x: (B, S, D)."""
    h = jnp.einsum("bsd,df->bsf", x, p["shared_in"].astype(x.dtype))
    g = jnp.einsum("bsd,df->bsf", x, p["shared_gate"].astype(x.dtype))
    return jnp.einsum("bsf,fd->bsd", jax.nn.silu(g) * h,
                      p["shared_out"].astype(x.dtype))


def routed_ffn(cfg: ArchConfig, p: dict, x: jax.Array
               ) -> tuple[jax.Array, dict]:
    """The held experts' routed part, x: (B, S, D) -> (out, aux).
    Chunked over S."""
    e = cfg.moe
    B, S, D = x.shape
    chunk = min(e.chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk
    cap = _capacity(e, chunk)
    expert_rows[(B, S)] = B * n_chunks * e.n_held * cap

    if n_chunks == 1:
        # one chunk (a decode step): no loop, whose operands would be
        # materialised copies of a scanned layer's expert weights
        y, lb_sum, z_sum, n_sum, sel = jax.checkpoint(
            lambda xc: _moe_chunk(cfg, p, xc, cap))(x)
        aux = {"moe_load_balance": lb_sum, "moe_z_loss": z_sum,
               "moe_routed": n_sum, "moe_picks": sel}
        return y, aux

    xs = x.reshape(B, n_chunks, chunk, D).swapaxes(0, 1)  # (n, B, C, D)

    @jax.checkpoint
    def body(carry, xc):
        # remat: dispatch/combine one-hots are huge; recompute in backward
        lb_sum, z_sum, n_sum = carry
        yc, lb, z, n, sel = _moe_chunk(cfg, p, xc, cap)
        return (lb_sum + lb, z_sum + z, n_sum + n), (yc, sel)

    zero = jnp.zeros((), jnp.float32)
    (lb_sum, z_sum, n_sum), (ys, sels) = lax.scan(
        body, (zero, zero, jnp.zeros((), jnp.int32)), xs)
    y = ys.swapaxes(0, 1).reshape(B, S, D)
    aux = {"moe_load_balance": lb_sum / n_chunks,
           "moe_z_loss": z_sum / n_chunks, "moe_routed": n_sum,
           "moe_picks": sels.swapaxes(0, 1).reshape(B, S, e.top_k)}
    return y, aux


def moe_ffn(cfg: ArchConfig, p: dict, x: jax.Array
            ) -> tuple[jax.Array, dict]:
    """x: (B, S, D) -> (out, aux): the held experts' routed part plus the
    shared experts."""
    y, aux = routed_ffn(cfg, p, x)
    if cfg.moe.n_shared:
        y = y + shared_ffn(cfg, p, x)
    return y, aux


def _moe_chunk(cfg: ArchConfig, p: dict, xc: jax.Array, cap: int):
    """One seq chunk: xc (B, C, D)."""
    e = cfg.moe
    B, C, D = xc.shape
    E, K = e.n_routed, e.top_k
    first, n_held = e.held_range

    # router matmul in the activation dtype (a f32 cast of xc here would
    # drag a full-width f32 copy of the hidden through the model-axis
    # all-gather); only the small (B, C, E) logits are upcast.
    logits = jnp.einsum("bcd,de->bce", xc,
                        p["router"].astype(xc.dtype)).astype(jnp.float32)
    gate, sel, probs = route(e, logits)                      # (B, C, K)

    # aux losses
    me = probs.mean(axis=(0, 1))                             # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[sel.reshape(-1)].add(
        1.0 / (B * C * K))
    load_balance = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # capacity-bucketed dispatch (Switch-style, per (batch, chunk)) over
    # the held experts; a pick outside them one-hots to zeros
    onehot = jax.nn.one_hot(sel - first, n_held, dtype=jnp.float32)
    # position of each (token, k) within its expert's bucket, in (C*K) order
    flat = onehot.reshape(B, C * K, n_held)
    pos_in_e = jnp.cumsum(flat, axis=1) - flat               # (B, C*K, E)
    pos_in_e = (pos_in_e * flat).sum(-1).astype(jnp.int32)   # (B, C*K)
    keep = (pos_in_e < cap).astype(jnp.float32)
    slot = jax.nn.one_hot(pos_in_e, cap, dtype=jnp.float32)  # (B, C*K, cap)
    # dispatch[b, ck, e, cap]
    dispatch = flat[..., None] * slot[..., None, :] * keep[..., None, None]
    routed = dispatch.sum().astype(jnp.int32)
    combine = dispatch.reshape(B, C, K, n_held, cap) \
        * gate[..., None, None]                              # weight per slot
    dispatch = dispatch.reshape(B, C, K, n_held, cap).sum(2)  # (B,C,E,cap)
    combine = combine.sum(2)                                 # (B, C, E, cap)

    cd = xc.dtype
    xe = jnp.einsum("bceg,bcd->begd", dispatch.astype(cd), xc)  # (B,E,cap,D)
    h = jnp.einsum("begd,edf->begf", xe, p["w_in"].astype(cd))
    g = jnp.einsum("begd,edf->begf", xe, p["w_gate"].astype(cd))
    oe = jnp.einsum("begf,efd->begd", jax.nn.silu(g) * h,
                    p["w_out"].astype(cd))                   # (B,E,cap,D)
    yc = jnp.einsum("bceg,begd->bcd", combine.astype(cd), oe)
    return yc, load_balance, z_loss, routed, sel
