"""Blockwise (flash-style) attention in pure jnp, with a custom flash VJP.

Numerically identical to full softmax attention but never materializes the
(S, T) score matrix — in the FORWARD (scan over KV blocks carrying the
running (max, sum, out) triple) and, crucially, in the BACKWARD: plain JAX
AD through the KV scan would save the per-step attention probabilities
(= the full S x T matrix, observed 55 GB/device at 1M tokens), so
``blockwise_gqa`` registers the standard flash backward (save (q,k,v,out,lse)
only; recompute p tile-by-tile; ~2.5x forward attention FLOPs).

This is the memory-scalable attention used by train/prefill paths (32k+
contexts); the Pallas kernels in ``repro.kernels`` implement the same
contract for real-TPU execution, and this function doubles as their oracle
for big shapes.

FLOPs note: with ``causal=True`` the block grid is rectangular — fully
masked blocks still execute (~2x causal-optimal FLOPs). ``schedule="tri"``
(forward only) visits only j <= i blocks at the price of an O(n_q_blocks)
HLO. The §Perf log tracks this trade.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _id_constrain(t, b, h=None):
    return t


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """Static tile-grid config (hashable: used as a nondiff custom_vjp arg)."""
    causal: bool
    scale: float
    mask_offset: int
    T: int                       # real (unpadded) KV length
    qb: int
    kb: int
    constrain: Callable = _id_constrain


def _q_pos(c, qi, qb):
    return c.mask_offset + qi * qb + jnp.arange(qb)


def _k_pos(c, kj, kb):
    return kj * kb + jnp.arange(kb)


def _scores(c, q_tile, k_tile, qi, kj):
    """(B,qb,Hkv,G,D) x (B,kb,Hkv,D) -> masked f32 (B,Hkv,G,qb,kb)."""
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q_tile, k_tile)
    s = s.astype(jnp.float32) * c.scale
    kp = _k_pos(c, kj, k_tile.shape[1])
    mask = (kp < c.T)[None, :]
    if c.causal:
        qp = _q_pos(c, qi, q_tile.shape[1])
        mask = mask & (kp[None, :] <= qp[:, None])
    return jnp.where(mask[None, None, None, :, :], s, NEG_INF)


# ---------------------------------------------------------------------------
# Tiled forward
# ---------------------------------------------------------------------------


def _fwd_tiles(c, qg, kg, vg):
    """qg: (nq,B,qb,Hkv,G,D); kg/vg: (nk,B,kb,Hkv,D[v]).

    Returns (out (nq,B,qb,Hkv,G,Dv), lse (nq,B,Hkv,G,qb) f32).
    """
    nq, B, qb, Hkv, G, D = qg.shape
    nk = kg.shape[0]
    Dv = vg.shape[-1]

    def q_block_body(args):
        qi, q_tile = args

        def step(carry, j):
            o, m, l = carry
            s = _scores(c, q_tile, kg[j], qi, j)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            pv = jnp.einsum("bhgqk,bkhv->bhgqv", p.astype(vg.dtype), vg[j])
            o_new = o * alpha[..., None].astype(o.dtype) + pv
            return (o_new, m_new, l_new), None

        o0 = c.constrain(jnp.zeros((B, Hkv, G, qb, Dv), vg.dtype), 0, 1)
        m0 = c.constrain(jnp.full((B, Hkv, G, qb), NEG_INF, jnp.float32), 0, 1)
        l0 = c.constrain(jnp.zeros((B, Hkv, G, qb), jnp.float32), 0, 1)
        (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), jnp.arange(nk))
        out = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        return jnp.transpose(out, (0, 3, 1, 2, 4)), lse   # (B,qb,Hkv,G,Dv)

    out, lse = jax.lax.map(q_block_body, (jnp.arange(nq), qg))
    return out, lse


# ---------------------------------------------------------------------------
# Flash backward (recompute p per tile; no S x T materialization)
# ---------------------------------------------------------------------------


def _bwd_tiles(c, qg, kg, vg, lse, delta, dog):
    """Flash backward. dog: (nq,B,qb,Hkv,G,Dv) upstream grads.

    lse/delta: (nq,B,Hkv,G,qb) f32. Returns (dqg, dkg, dvg) in tile layout.
    """
    nq, B, qb, Hkv, G, D = qg.shape
    nk = kg.shape[0]
    dt = qg.dtype

    def p_ds(qi, kj, q_tile):
        s = _scores(c, q_tile, kg[kj], qi, kj)
        p = jnp.exp(s - lse[qi][..., None])               # (B,Hkv,G,qb,kb)
        do = dog[qi]                                      # (B,qb,Hkv,G,Dv)
        dp = jnp.einsum("bqhgv,bkhv->bhgqk", do, vg[kj]).astype(jnp.float32)
        ds = p * (dp - delta[qi][..., None]) * c.scale
        return p, ds, do

    def dq_block(args):
        qi, q_tile = args

        def step(dq, kj):
            _, ds, _ = p_ds(qi, kj, q_tile)
            dq = dq + jnp.einsum("bhgqk,bkhd->bqhgd", ds.astype(dt), kg[kj])
            return dq, None

        dq0 = c.constrain(jnp.zeros_like(q_tile), 0, 2)
        dq, _ = jax.lax.scan(step, dq0, jnp.arange(nk))
        return dq

    def dkv_block(args):
        kj, k_tile, v_tile = args

        def step(carry, qi):
            dk, dv = carry
            q_tile = qg[qi]
            p, ds, do = p_ds(qi, kj, q_tile)
            dk = dk + jnp.einsum("bhgqk,bqhgd->bkhd", ds.astype(dt), q_tile)
            dv = dv + jnp.einsum("bhgqk,bqhgv->bkhv", p.astype(dt), do)
            return (dk, dv), None

        dk0 = c.constrain(jnp.zeros_like(k_tile), 0, 2)
        dv0 = c.constrain(jnp.zeros_like(v_tile), 0, 2)
        (dk, dv), _ = jax.lax.scan(step, (dk0, dv0), jnp.arange(nq))
        return dk, dv

    dqg = jax.lax.map(dq_block, (jnp.arange(nq), qg))
    dkg, dvg = jax.lax.map(dkv_block, (jnp.arange(nk), kg, vg))
    return dqg, dkg, dvg


# ---------------------------------------------------------------------------
# custom_vjp wrapper (operates on tile layout; padding handled by caller)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(c, qg, kg, vg):
    out, _ = _fwd_tiles(c, qg, kg, vg)
    return out


def _flash_fwd(c, qg, kg, vg):
    out, lse = _fwd_tiles(c, qg, kg, vg)
    return out, (qg, kg, vg, out, lse)


def _flash_bwd(c, res, dout):
    qg, kg, vg, out, lse = res
    delta = jnp.einsum("nbqhgv,nbqhgv->nbhgq",
                       dout.astype(jnp.float32), out.astype(jnp.float32))
    return _bwd_tiles(c, qg, kg, vg, lse, delta, dout)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def blockwise_gqa(q, k, v, *, causal=True, mask_offset=0, q_block=512,
                  kv_block=1024, schedule="rect", constrain=None, scale=None):
    """q: (B,S,Hq,D) k,v: (B,T,Hkv,D[v]) -> (B,S,Hq,Dv).

    scale: the softmax scale (default D^-0.5).
    mask_offset: queries at global position ``mask_offset + i`` may attend
    keys at positions j <= mask_offset + i (must be a python int).
    constrain: optional fn(tensor, batch_dim) -> tensor applying a batch
    sharding constraint — without it GSPMD tends to reshard the tile scan
    onto heads and replicate the batch dim (observed 7x memory blowup).
    """
    constrain = constrain or _id_constrain
    B, S, Hq, D = q.shape
    T = k.shape[1]
    Hkv = k.shape[2]
    Dv = v.shape[-1]
    G = Hq // Hkv

    qb = min(q_block, S)
    kb = min(kv_block, T)
    nq = -(-S // qb)
    nk = -(-T // kb)
    S_pad, T_pad = nq * qb, nk * kb
    if S_pad != S:
        q = jnp.pad(q, ((0, 0), (0, S_pad - S), (0, 0), (0, 0)))
    if T_pad != T:
        k = jnp.pad(k, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, T_pad - T), (0, 0), (0, 0)))

    qg = constrain(q.reshape(B, nq, qb, Hkv, G, D), 0, 3)
    qg = constrain(jnp.moveaxis(qg, 1, 0), 1, 3)           # (nq,B,qb,Hkv,G,D)
    kg = constrain(jnp.moveaxis(k.reshape(B, nk, kb, Hkv, D), 1, 0), 1, 3)
    vg = constrain(jnp.moveaxis(v.reshape(B, nk, kb, Hkv, Dv), 1, 0), 1, 3)

    scale = D ** -0.5 if scale is None else scale
    c = _Cfg(causal=causal, scale=scale, mask_offset=int(mask_offset),
             T=T, qb=qb, kb=kb, constrain=constrain)
    if schedule == "tri" and causal:
        out = _tri_fwd(c, qg, kg, vg)
    else:
        out = _flash(c, qg, kg, vg)
    out = constrain(jnp.moveaxis(out, 0, 1), 0, 3)         # (B,nq,qb,Hkv,G,Dv)
    out = constrain(out.reshape(B, S_pad, Hq, Dv), 0, 2)
    return out[:, :S]


def _tri_fwd(c, qg, kg, vg):
    """Causal-skip schedule: python loop over q tiles, inner scan j <= i.

    Exactly the causal FLOPs (the §Perf lever for prefill); forward-only —
    AD falls back to scan residuals, so use for inference paths.
    """
    nq, B, qb, Hkv, G, D = qg.shape
    nk = kg.shape[0]
    Dv = vg.shape[-1]
    outs = []
    for qi in range(nq):
        q_tile = qg[qi]

        def step(carry, j, qi=qi, q_tile=q_tile):
            o, m, l = carry
            s = _scores(c, q_tile, kg[j], qi, j)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            pv = jnp.einsum("bhgqk,bkhv->bhgqv", p.astype(vg.dtype), vg[j])
            o_new = o * alpha[..., None].astype(o.dtype) + pv
            return (o_new, m_new, l_new), None

        o0 = c.constrain(jnp.zeros((B, Hkv, G, qb, Dv), vg.dtype), 0, 1)
        m0 = c.constrain(jnp.full((B, Hkv, G, qb), NEG_INF, jnp.float32), 0, 1)
        l0 = c.constrain(jnp.zeros((B, Hkv, G, qb), jnp.float32), 0, 1)
        # only kv tiles overlapping [0, (qi+1)*qb + mask_offset) contribute
        n_vis = min(nk, -(-((qi + 1) * c.qb + c.mask_offset) // c.kb))
        (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), jnp.arange(n_vis))
        out = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
        outs.append(jnp.transpose(out, (0, 3, 1, 2, 4)))
    return jnp.stack(outs, axis=0)                         # (nq,B,qb,...)


# ---------------------------------------------------------------------------
# Absorbed-MLA blockwise attention over the COMPRESSED cache (inference /
# memory-bound prefill experiments; no custom vjp — forward-only use).
# ---------------------------------------------------------------------------


def blockwise_mla(q_c, q_r, ckv, krope, *, v_up, scale, causal=True,
                  mask_offset=0, q_block=512, kv_block=1024):
    """q_c: (B,S,H,r) absorbed queries; q_r: (B,S,H,dr); ckv: (B,T,r);
    krope: (B,T,dr); v_up: (r,H,Dv). Returns (B,S,H,Dv).

    Logits l[t] = q_c . ckv[t] + q_r . krope[t]; values are the compressed
    ckv rows, expanded through v_up once at the end — the flash carry is
    (o_c (B,H,qb,r), m, l), r-dim not Dv-dim.
    """
    B, S, H, r = q_c.shape
    T = ckv.shape[1]
    dr = q_r.shape[-1]

    qb = min(q_block, S)
    kb = min(kv_block, T)
    nq, nk = -(-S // qb), -(-T // kb)
    S_pad, T_pad = nq * qb, nk * kb
    pad4 = lambda x, n: jnp.pad(x, ((0, 0), (0, n), (0, 0), (0, 0)))
    pad3 = lambda x, n: jnp.pad(x, ((0, 0), (0, n), (0, 0)))
    if S_pad != S:
        q_c, q_r = pad4(q_c, S_pad - S), pad4(q_r, S_pad - S)
    if T_pad != T:
        ckv, krope = pad3(ckv, T_pad - T), pad3(krope, T_pad - T)

    qcg = q_c.reshape(B, nq, qb, H, r)
    qrg = q_r.reshape(B, nq, qb, H, dr)
    cg = jnp.moveaxis(ckv.reshape(B, nk, kb, r), 1, 0)
    kg = jnp.moveaxis(krope.reshape(B, nk, kb, dr), 1, 0)
    q_pos = mask_offset + jnp.arange(S_pad).reshape(nq, qb)
    k_pos = jnp.arange(T_pad).reshape(nk, kb)
    k_valid = k_pos < T

    def q_block_body(args):
        qi, qc_t, qr_t = args

        def step(carry, j):
            o, m, l = carry
            s = (jnp.einsum("bqhr,bkr->bhqk", qc_t, cg[j])
                 + jnp.einsum("bqhr,bkr->bhqk", qr_t, kg[j]))
            s = s.astype(jnp.float32) * scale
            mask = k_valid[j][None, :]
            if causal:
                mask = mask & (k_pos[j][None, :] <= q_pos[qi][:, None])
            s = jnp.where(mask[None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = l * alpha + p.sum(-1)
            pv = jnp.einsum("bhqk,bkr->bhqr", p.astype(cg.dtype), cg[j])
            o_new = o * alpha[..., None].astype(o.dtype) + pv
            return (o_new, m_new, l_new), None

        o0 = jnp.zeros((B, H, qb, r), ckv.dtype)
        m0 = jnp.full((B, H, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, H, qb), jnp.float32)
        (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), jnp.arange(nk))
        o = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
        return jnp.transpose(o, (0, 2, 1, 3))              # (B,qb,H,r)

    qc_tiles = jnp.moveaxis(qcg, 1, 0)
    qr_tiles = jnp.moveaxis(qrg, 1, 0)
    o_c = jax.lax.map(q_block_body, (jnp.arange(nq), qc_tiles, qr_tiles))
    o_c = jnp.moveaxis(o_c, 0, 1).reshape(B, S_pad, H, r)[:, :S]
    return jnp.einsum("bshr,rhv->bshv", o_c, v_up)
