"""KV-sequence-sharded decode attention (TPU flash-decoding) via shard_map.

At 32k-500k context the KV cache cannot live on one chip and GQA kv-head
counts (4-16) don't divide a 16-way model axis — so the decode cache shards
along the SEQUENCE dim over "model". Each device:

  1. updates its local cache slice iff the global write position lands in it,
  2. computes partial attention (o, m, l) over its KV slice,
  3. combines with the max-rescale trick: one pmax + two psums over "model".

This is the explicit-collective equivalent of flash-decoding; GSPMD cannot
derive it automatically (a sharded-softmax over a dynamic-length axis), which
is why this is a shard_map and not an annotation.

All functions take/return GLOBAL arrays and must be called under the mesh
(inside jit with sharded operands or eagerly with committed arrays).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


NEG_INF = -1e30


def _local_update(cache, new, global_idx, seq_axis, n_shards):
    """Write ``new`` (B,1,...) at global seq position inside a shard_map."""
    M_local = cache.shape[1]
    shard = jax.lax.axis_index(seq_axis)
    start = shard * M_local
    loc = global_idx - start
    in_range = (loc >= 0) & (loc < M_local)
    loc_c = jnp.clip(loc, 0, M_local - 1)
    zeros = (0,) * (cache.ndim - 2)
    old = jax.lax.dynamic_slice(
        cache, (0, loc_c) + zeros, (cache.shape[0], 1) + cache.shape[2:])
    upd = jnp.where(in_range, new.astype(cache.dtype), old)
    return jax.lax.dynamic_update_slice(cache, upd, (0, loc_c) + zeros), start


def _combine(o, m, l, seq_axis):
    """(o,m,l) partial flash stats -> combined output over ``seq_axis``."""
    m_g = jax.lax.pmax(m, seq_axis)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, seq_axis)
    o_g = jax.lax.psum(o * corr[..., None].astype(o.dtype), seq_axis)
    return o_g / jnp.maximum(l_g, 1e-30)[..., None].astype(o.dtype)


def gqa_decode_seq_sharded(q, k_new, v_new, kc, vc, cache_len, *, mesh,
                           seq_axis="model", batch_axes=("data",)):
    """One-token GQA decode over a seq-sharded cache.

    q      : (B, 1, Hq, D)   — replicated over ``seq_axis``
    k_new  : (B, 1, Hkv, D)  — this step's key (pre-roped)
    v_new  : (B, 1, Hkv, D)
    kc, vc : (B, M, Hkv, D)  — M sharded over ``seq_axis``
    cache_len: ()            — global write/attend position

    Returns (out (B,1,Hq*D), kc', vc').
    """
    B, _, Hq, D = q.shape
    Hkv = kc.shape[2]
    G = Hq // Hkv
    n_shards = mesh.shape[seq_axis]
    scale = D ** -0.5
    b_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None)
    if B % max(1, _prod(mesh, b_axes)) != 0:
        bspec = None

    def local(q, k_new, v_new, kc, vc, cache_len):
        kc, start = _local_update(kc, k_new, cache_len, seq_axis, n_shards)
        vc, _ = _local_update(vc, v_new, cache_len, seq_axis, n_shards)
        Ml = kc.shape[1]
        pos = start + jnp.arange(Ml)
        qg = q.reshape(q.shape[0], Hkv, G, D)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, kc).astype(jnp.float32) * scale
        s = jnp.where((pos <= cache_len)[None, None, None, :], s, NEG_INF)
        m = s.max(-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(-1)
        o = jnp.einsum("bkgt,btkv->bkgv", p.astype(vc.dtype), vc)
        out = _combine(o, m, l, seq_axis)                   # (b,Hkv,G,D)
        return out.reshape(out.shape[0], 1, Hq * D), kc, vc

    sm = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec),
                  P(bspec, seq_axis), P(bspec, seq_axis), P()),
        out_specs=(P(bspec), P(bspec, seq_axis), P(bspec, seq_axis)),
        check_vma=False)
    return sm(q, k_new, v_new, kc, vc, cache_len)


def mla_decode_seq_sharded(q_c, q_r, ckv_new, krope_new, ckv_c, krope_c,
                           cache_len, scale, *, mesh, seq_axis="model",
                           batch_axes=("data",)):
    """Absorbed-MLA decode over a seq-sharded compressed cache.

    q_c: (B,1,H,r); q_r: (B,1,H,dr); ckv_new: (B,1,r); krope_new: (B,1,dr);
    ckv_c: (B,M,r); krope_c: (B,M,dr). Returns (out_c (B,1,H,r), ckv', krope').
    """
    B, _, H, r = q_c.shape
    n_shards = mesh.shape[seq_axis]
    b_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    bspec = b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None)
    if B % max(1, _prod(mesh, b_axes)) != 0:
        bspec = None

    def local(q_c, q_r, ckv_new, krope_new, ckv_c, krope_c, cache_len):
        ckv_c, start = _local_update(ckv_c, ckv_new, cache_len, seq_axis,
                                     n_shards)
        krope_c, _ = _local_update(krope_c, krope_new, cache_len, seq_axis,
                                   n_shards)
        Ml = ckv_c.shape[1]
        pos = start + jnp.arange(Ml)
        s = (jnp.einsum("bshr,btr->bhst", q_c, ckv_c)
             + jnp.einsum("bshr,btr->bhst", q_r, krope_c))
        s = s.astype(jnp.float32) * scale                  # (b,H,1,Ml)
        s = jnp.where((pos <= cache_len)[None, None, None, :], s, NEG_INF)
        m = s.max(-1)
        p = jnp.exp(s - m[..., None])
        l = p.sum(-1)
        o = jnp.einsum("bhst,btr->bhsr", p.astype(ckv_c.dtype), ckv_c)
        out = _combine(o, m, l, seq_axis)                  # (b,H,1,r)
        return jnp.moveaxis(out, 1, 2), ckv_c, krope_c     # (b,1,H,r)

    sm = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec), P(bspec),
                  P(bspec, seq_axis), P(bspec, seq_axis), P()),
        out_specs=(P(bspec), P(bspec, seq_axis), P(bspec, seq_axis)),
        check_vma=False)
    return sm(q_c, q_r, ckv_new, krope_new, ckv_c, krope_c, cache_len)


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
