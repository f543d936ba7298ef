"""Pure-jnp oracles for every kernel.

These are (a) the correctness references the tuner verifies against (paper:
Kernel Tuner's output verification), and (b) the execution path on non-TPU
hosts (``REPRO_KERNEL_BACKEND=reference``). The *term* functions here are the
single source of truth for the stencil math — the Pallas kernels call the
same functions with block-local shift closures, so kernel and oracle cannot
drift apart.

All stencils are periodic in every axis (MicroHH is periodic in x/y; we use
fully periodic fields so halo handling is uniform).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# --------------------------------------------------------------------------
# advec_u: 2nd-order flux-form advection with 5th-order interpolation
# (paper §5.2 kernel 1). Collocated periodic grid.
# --------------------------------------------------------------------------

_C0, _C1, _C2 = 37.0 / 60.0, -8.0 / 60.0, 1.0 / 60.0


def advec_terms(su_x, su_y, su_z, sv_y, sw_z, dxi, dyi, dzi):
    """Advection tendency of u. Each ``s*`` is a shift closure s(offset)
    returning the field shifted by ``offset`` cells along one axis
    (result[idx] = field[idx + offset], periodic)."""

    def interp(s, o):
        # 5th-order interpolation to the face between cells o-1 and o
        return (_C0 * (s(o - 1) + s(o)) + _C1 * (s(o - 2) + s(o + 1))
                + _C2 * (s(o - 3) + s(o + 2)))

    fx_p = 0.5 * (su_x(0) + su_x(1)) * interp(su_x, 1)
    fx_m = 0.5 * (su_x(-1) + su_x(0)) * interp(su_x, 0)
    fy_p = 0.5 * (sv_y(0) + sv_y(1)) * interp(su_y, 1)
    fy_m = 0.5 * (sv_y(-1) + sv_y(0)) * interp(su_y, 0)
    fz_p = 0.5 * (sw_z(0) + sw_z(1)) * interp(su_z, 1)
    fz_m = 0.5 * (sw_z(-1) + sw_z(0)) * interp(su_z, 0)
    return -(dxi * (fx_p - fx_m) + dyi * (fy_p - fy_m)
             + dzi * (fz_p - fz_m))


ADVEC_FLOPS_PER_POINT = 78  # counted from advec_terms


def _roll_shift(f, axis):
    return lambda s: f if s == 0 else jnp.roll(f, -s, axis)


def advec_u_ref(u, v, w, scal):
    """Oracle. scal is a (1, 4) f32 array [dxi, dyi, dzi, 0]."""
    dxi, dyi, dzi = scal[0, 0], scal[0, 1], scal[0, 2]
    u32 = u.astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    ut = advec_terms(
        su_x=_roll_shift(u32, 2), su_y=_roll_shift(u32, 1),
        su_z=_roll_shift(u32, 0), sv_y=_roll_shift(v32, 1),
        sw_z=_roll_shift(w32, 0), dxi=dxi, dyi=dyi, dzi=dzi)
    return ut.astype(u.dtype)


# --------------------------------------------------------------------------
# diff_uvw: 2nd-order Smagorinsky-style diffusion of all three velocity
# components with a variable eddy viscosity (paper §5.2 kernel 2).
# --------------------------------------------------------------------------


def diff_term(sf, se, di):
    """One-axis variable-viscosity diffusion: d/dx( ev * du/dx )."""
    ev_p = 0.5 * (se(0) + se(1))
    ev_m = 0.5 * (se(-1) + se(0))
    return (di * di) * (ev_p * (sf(1) - sf(0)) - ev_m * (sf(0) - sf(-1)))


def diff_field(sf_x, sf_y, sf_z, se_x, se_y, se_z, dxi, dyi, dzi):
    return (diff_term(sf_x, se_x, dxi) + diff_term(sf_y, se_y, dyi)
            + diff_term(sf_z, se_z, dzi))


DIFF_FLOPS_PER_POINT_PER_FIELD = 27


def diff_uvw_ref(u, v, w, evisc, scal):
    dxi, dyi, dzi = scal[0, 0], scal[0, 1], scal[0, 2]
    e32 = evisc.astype(jnp.float32)
    se = [_roll_shift(e32, ax) for ax in (2, 1, 0)]
    outs = []
    for f in (u, v, w):
        f32 = f.astype(jnp.float32)
        sf = [_roll_shift(f32, ax) for ax in (2, 1, 0)]
        ft = diff_field(sf[0], sf[1], sf[2], se[0], se[1], se[2],
                        dxi, dyi, dzi)
        outs.append(ft.astype(f.dtype))
    return tuple(outs)


# --------------------------------------------------------------------------
# matmul
# --------------------------------------------------------------------------


def matmul_ref(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)


# --------------------------------------------------------------------------
# attention (full-featured oracle: GQA, causal, sliding window, softcap)
# --------------------------------------------------------------------------


BLOCKWISE_THRESHOLD = 1024  # blockwise path when Sq and Sk both reach this


def attention_ref(q, k, v, *, causal: bool = True,
                  window=None,
                  softcap: float | None = None,
                  scale: float | None = None,
                  kv_offset: int = 0):
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, Dv). GQA via head repetition.

    ``window`` may be a static int or a traced scalar (0/None = full).
    ``kv_offset``: absolute position of q[0] minus position of k[0].
    Long sequences dispatch to the blockwise online-softmax path — the XLA
    equivalent of the Pallas flash kernel (O(S·chunk) memory)."""
    Sq, Sk = q.shape[2], k.shape[2]
    if Sq >= BLOCKWISE_THRESHOLD and Sk >= BLOCKWISE_THRESHOLD:
        return blockwise_attention_ref(
            q, k, v, causal=causal, window=window, softcap=softcap,
            scale=scale, kv_offset=kv_offset)
    return _naive_attention_ref(q, k, v, causal=causal, window=window,
                                softcap=softcap, scale=scale,
                                kv_offset=kv_offset)


def _naive_attention_ref(q, k, v, *, causal, window, softcap, scale,
                         kv_offset):
    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    Sk = k.shape[2]
    q_pos = jnp.arange(Sq)[:, None] + kv_offset
    k_pos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), dtype=bool)
    if causal:
        mask &= q_pos >= k_pos
    if window is not None:
        win = jnp.asarray(window)
        mask &= jnp.where(win > 0, (q_pos - k_pos) < win, True)
    s = jnp.where(mask[None, None], s, -1e30)
    # fully-masked rows produce 0 (matches the blockwise/flash convention)
    p = jnp.where(mask[None, None], jnp.exp(s - s.max(-1, keepdims=True)),
                  0.0)
    p = p / (p.sum(-1, keepdims=True) + 1e-30)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return o.astype(q.dtype)


def blockwise_attention_ref(q, k, v, *, causal: bool = True, window=None,
                            softcap: float | None = None,
                            scale: float | None = None, kv_offset: int = 0,
                            q_chunk: int = 512, k_chunk: int = 1024):
    """Flash-style attention in pure jnp: double chunked scan with online
    softmax, O(Sq·k_chunk) live memory instead of O(Sq·Sk). Same math as
    :func:`_naive_attention_ref` up to fp reassociation."""
    import jax

    B, Hq, Sq, D = q.shape
    Hkv = k.shape[1]
    Sk, Dv = k.shape[2], v.shape[3]
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    if Hq != Hkv:
        rep = Hq // Hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    # Pad both sequence dims up to chunk multiples instead of shrinking the
    # chunk: a tiny chunk explodes the scan's saved-carry count under
    # autodiff (nk residual copies of the accumulator).
    qc, kc = min(q_chunk, Sq), min(k_chunk, Sk)
    Sq_p = -(-Sq // qc) * qc
    Sk_p = -(-Sk // kc) * kc
    if Sq_p != Sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Sq_p - Sq), (0, 0)))
    if Sk_p != Sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, Sk_p - Sk), (0, 0)))
    nq, nk = Sq_p // qc, Sk_p // kc

    # keep the HBM-resident copies in the input dtype; cast per chunk
    # inside the loop (a full-sequence f32 copy of q/k/v dominated the
    # prefill memory footprint otherwise — see EXPERIMENTS.md §Perf)
    qf = jnp.moveaxis(q.reshape(B, Hq, nq, qc, D), 2, 0)
    kf = jnp.moveaxis(k.reshape(B, Hq, nk, kc, D), 2, 0)
    vf = jnp.moveaxis(v.reshape(B, Hq, nk, kc, Dv), 2, 0)
    q_pos = (jnp.arange(Sq_p) + kv_offset).reshape(nq, qc)
    k_pos = jnp.arange(Sk_p).reshape(nk, kc)
    k_valid = Sk

    win = None if window is None else jnp.asarray(window)

    def one_q_chunk(args):
        qi, qp = args                                  # (B,H,qc,D), (qc,)
        qi = qi.astype(jnp.float32)

        @jax.checkpoint
        def body(carry, inp):
            m, l, acc = carry
            ki, vi, kp = inp
            ki = ki.astype(jnp.float32)
            vi = vi.astype(jnp.float32)
            s = jnp.einsum("bhqd,bhkd->bhqk", qi, ki) * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)
            mask = kp[None, :] < k_valid            # padded keys masked
            if causal:
                mask &= qp[:, None] >= kp[None, :]
            if win is not None:
                mask &= jnp.where(win > 0,
                                  (qp[:, None] - kp[None, :]) < win, True)
            s = jnp.where(mask[None, None], s, -1e30)
            m_new = jnp.maximum(m, s.max(-1, keepdims=True))
            # explicit zero for masked entries: in a fully-masked chunk
            # s == m_new == -1e30 and exp(s - m_new) would be 1, not 0
            p = jnp.where(mask[None, None], jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(-1, keepdims=True)
            acc = acc * alpha + jnp.einsum("bhqk,bhkd->bhqd", p, vi)
            return (m_new, l, acc), None

        m0 = jnp.full((B, Hq, qc, 1), -1e30, jnp.float32)
        l0 = jnp.zeros((B, Hq, qc, 1), jnp.float32)
        a0 = jnp.zeros((B, Hq, qc, Dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kf, vf, k_pos))
        return acc / jnp.maximum(l, 1e-30)

    out = jax.lax.map(one_q_chunk, (qf, q_pos))        # (nq, B, H, qc, Dv)
    out = jnp.moveaxis(out, 0, 2).reshape(B, Hq, Sq_p, Dv)
    return out[:, :, :Sq].astype(q.dtype)


def flash_attention_ref_factory(causal: bool):
    """Oracle matching the Pallas flash kernel's flattened-head layout:
    q: (BH, S, D), k/v: (BHkv, S, D)."""

    def ref(q, k, v):
        BH, S, D = q.shape
        BHkv = k.shape[0]
        group = BH // BHkv
        k_e = jnp.repeat(k, group, axis=0)
        v_e = jnp.repeat(v, group, axis=0)
        o = attention_ref(q[:, None], k_e[:, None], v_e[:, None],
                          causal=causal)
        return o[:, 0]

    return ref


# --------------------------------------------------------------------------
# decode attention (one query token per slot against the KV arena)
# --------------------------------------------------------------------------


def cap_scores(s, cap):
    """Softcap with the cap as data: ``cap * tanh(s / cap)`` where
    ``cap > 0``, ``s`` unchanged where it is 0."""
    on = cap > 0
    return jnp.where(on, cap * jnp.tanh(s / jnp.where(on, cap, 1.0)), s)


def decode_attention_ref(q, cache_k, cache_v, pos, window, softcap=None,
                         start=None):
    """q: (B, Hq, 1, D) against one layer's cache, cache_k/v
    (B, Hkv, D, S) (the arena's layout, sequence axis minor) that already
    holds the step's K/V at ``pos``. Masks unwritten and out-of-window
    positions, plus per-batch positions below ``start`` (stale cache
    from a slot's previous occupant). ``window`` and ``softcap`` may be
    traced (0 = unlimited / none). Masking (not zeroing) is load-bearing
    for slot reuse: a zeroed K row still gets softmax weight exp(0), so
    stale rows must be excluded from the normalization, and rotary
    phases stay correct because only relative distances inside
    [start, pos] survive.

    The cache stays in its storage dtype — an ``astype(f32)`` here gets
    hoisted by the compiler into a full f32 copy of the *whole stacked
    cache* (2x the serving HBM); f32 accumulation comes from
    ``preferred_element_type`` instead."""
    B, Hq, _, D = q.shape
    Hkv = cache_k.shape[1]
    group = Hq // Hkv
    qg = q.reshape(B, Hkv, group, D).astype(cache_k.dtype)
    s = jnp.einsum("bhgd,bhdk->bhgk", qg, cache_k,
                   preferred_element_type=jnp.float32) * (D ** -0.5)
    if softcap is not None:
        s = cap_scores(s, jnp.asarray(softcap, jnp.float32))
    k_pos = jnp.arange(cache_k.shape[3])
    valid = k_pos <= pos
    valid &= jnp.where(window > 0, (pos - k_pos) < window, True)
    if start is None:
        mask = valid[None, None, None, :]
    else:
        mask = (valid[None, :]
                & (k_pos[None, :] >= start[:, None]))[:, None, None, :]
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgk,bhdk->bhgd", p.astype(cache_v.dtype), cache_v,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, Hq, 1, D).astype(q.dtype)
