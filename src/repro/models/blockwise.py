"""Blockwise (flash-style) attention in pure jnp, forward only.

Numerically identical to full softmax attention but never materializes the
(S, T) score matrix: a scan over KV blocks carries the running
(max, sum, out) triple.

This is the memory-scalable attention of the forward/prefill paths (32k+
contexts); the Pallas kernels in ``repro.kernels`` implement the same
contract for real-TPU execution, and this function doubles as their oracle
for big shapes.

FLOPs note: with ``causal=True`` the block grid is rectangular — fully
masked blocks still execute (~2x causal-optimal FLOPs).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _id_constrain(t, b):
    return t


@dataclasses.dataclass(frozen=True)
class _Cfg:
    """Static tile-grid config."""
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

    Returns out (nq,B,qb,Hkv,G,Dv).
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

        o0 = c.constrain(jnp.zeros((B, Hkv, G, qb, Dv), vg.dtype), 0)
        m0 = c.constrain(jnp.full((B, Hkv, G, qb), NEG_INF, jnp.float32), 0)
        l0 = c.constrain(jnp.zeros((B, Hkv, G, qb), jnp.float32), 0)
        (o, m, l), _ = jax.lax.scan(step, (o0, m0, l0), jnp.arange(nk))
        out = o / jnp.maximum(l, 1e-30)[..., None].astype(o.dtype)
        return jnp.transpose(out, (0, 3, 1, 2, 4))        # (B,qb,Hkv,G,Dv)

    return jax.lax.map(q_block_body, (jnp.arange(nq), qg))


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


def blockwise_gqa(q, k, v, *, causal=True, mask_offset=0, q_block=512,
                  kv_block=1024, constrain=None, scale=None):
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

    qg = constrain(q.reshape(B, nq, qb, Hkv, G, D), 0)
    qg = constrain(jnp.moveaxis(qg, 1, 0), 1)              # (nq,B,qb,Hkv,G,D)
    kg = constrain(jnp.moveaxis(k.reshape(B, nk, kb, Hkv, D), 1, 0), 1)
    vg = constrain(jnp.moveaxis(v.reshape(B, nk, kb, Hkv, Dv), 1, 0), 1)

    scale = D ** -0.5 if scale is None else scale
    c = _Cfg(causal=causal, scale=scale, mask_offset=int(mask_offset),
             T=T, qb=qb, kb=kb, constrain=constrain)
    out = _fwd_tiles(c, qg, kg, vg)
    out = constrain(jnp.moveaxis(out, 0, 1), 0)            # (B,nq,qb,Hkv,G,Dv)
    out = constrain(out.reshape(B, S_pad, Hq, Dv), 0)
    return out[:, :S]
