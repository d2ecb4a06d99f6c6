"""KV-sequence-sharded decode attention (TPU flash-decoding) via shard_map.

At 32k-500k context the KV cache cannot live on one chip and GQA kv-head
counts (4-16) don't divide a 16-way model axis — so the decode cache shards
along the SEQUENCE dim over "model". Each device:

  1. computes partial attention (o, m, l) over the cached positions of its
     KV slice,
  2. combines them with the step's own key and value by the max-rescale
     trick: one pmax + two psums over "model".

The step's row is written after the layers, once for all of them
(``models.model.write_rows``).

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


def _bspec(mesh, batch_axes, B):
    """The batch dim's spec: the mesh's batch axes where B divides them."""
    b_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    if B % max(1, _prod(mesh, b_axes)) != 0:
        return None
    return b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None)


def _partial(s, vals, pv, start, cache_len):
    """Flash statistics (o, m, l) of scores s (...,Ml) over this shard's
    cached positions before cache_len, in f32."""
    pos = start + jnp.arange(s.shape[-1])
    s = jnp.where(pos < cache_len, s, NEG_INF)
    m = s.max(-1)
    p = jnp.exp(s - m[..., None])
    o = jnp.einsum(pv, p.astype(vals.dtype), vals,
                   preferred_element_type=jnp.float32)
    return o, m, p.sum(-1)


def _combine(o, m, l, s_own, v_own, seq_axis):
    """(o,m,l) partial flash stats over the shards' past positions, and
    the step's own score s_own and value v_own (broadcast to o), ->
    combined output over ``seq_axis``."""
    m_g = jnp.maximum(jax.lax.pmax(m, seq_axis), s_own)
    corr = jnp.exp(m - m_g)
    own = jnp.exp(s_own - m_g)
    l_g = jax.lax.psum(l * corr, seq_axis) + own
    o_g = jax.lax.psum(o * corr[..., None], seq_axis) + own[..., None] * v_own
    return o_g / l_g[..., None]


def gqa_decode_seq_sharded(q, k_new, v_new, kc, vc, cache_len, *, mesh,
                           seq_axis="model", batch_axes=("data",)):
    """One-token GQA decode over a seq-sharded cache, read only: the cached
    positions before cache_len and this step's own key and value.

    q      : (B, 1, Hq, D)   — replicated over ``seq_axis``
    k_new  : (B, 1, Hkv, D)  — this step's key (pre-roped)
    v_new  : (B, 1, Hkv, D)
    kc, vc : (B, M, Hkv, D)  — M sharded over ``seq_axis``
    cache_len: ()            — global position of this step

    Returns out (B,1,Hq*D) in the cache's dtype.
    """
    B, _, Hq, D = q.shape
    Hkv = kc.shape[2]
    G = Hq // Hkv
    scale = D ** -0.5
    bspec = _bspec(mesh, batch_axes, B)

    def local(q, k_new, v_new, kc, vc, cache_len):
        qg = q.reshape(q.shape[0], Hkv, G, D)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, kc).astype(jnp.float32) * scale
        o, m, l = _partial(s, vc, "bkgt,btkv->bkgv",
                           jax.lax.axis_index(seq_axis) * kc.shape[1],
                           cache_len)
        s_own = jnp.einsum("bkgd,bskd->bkg", qg,
                           k_new).astype(jnp.float32) * scale
        v_own = v_new[:, 0, :, None, :].astype(jnp.float32)
        out = _combine(o, m, l, s_own, v_own, seq_axis)     # (b,Hkv,G,D)
        return out.reshape(out.shape[0], 1, Hq * D).astype(vc.dtype)

    sm = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec),
                  P(bspec, seq_axis), P(bspec, seq_axis), P()),
        out_specs=P(bspec), check_vma=False)
    return sm(q, k_new, v_new, kc, vc, cache_len)


def mla_decode_seq_sharded(q_c, q_r, ckv_new, krope_new, ckv_c, krope_c,
                           cache_len, scale, *, mesh, seq_axis="model",
                           batch_axes=("data",)):
    """Absorbed-MLA decode over a seq-sharded compressed cache, read only:
    the cached positions before cache_len and this step's own latent row.

    q_c: (B,1,H,r); q_r: (B,1,H,dr); ckv_new: (B,1,r); krope_new: (B,1,dr);
    ckv_c: (B,M,r); krope_c: (B,M,dr). Returns out_c (B,1,H,r) in the
    cache's dtype.
    """
    bspec = _bspec(mesh, batch_axes, q_c.shape[0])

    def local(q_c, q_r, ckv_new, krope_new, ckv_c, krope_c, cache_len):
        s = (jnp.einsum("bshr,btr->bhst", q_c, ckv_c)
             + jnp.einsum("bshr,btr->bhst", q_r, krope_c))
        o, m, l = _partial(s.astype(jnp.float32) * scale, ckv_c,
                           "bhst,btr->bhsr",
                           jax.lax.axis_index(seq_axis) * ckv_c.shape[1],
                           cache_len)
        s_own = (jnp.einsum("bshr,bur->bhs", q_c, ckv_new)
                 + jnp.einsum("bshr,bur->bhs", q_r, krope_new))
        v_own = ckv_new[:, None].astype(jnp.float32)        # (b,1,1,r)
        out = _combine(o, m, l, s_own.astype(jnp.float32) * scale, v_own,
                       seq_axis)                            # (b,H,1,r)
        return jnp.moveaxis(out, 1, 2).astype(ckv_c.dtype)  # (b,1,H,r)

    sm = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec), P(bspec),
                  P(bspec, seq_axis), P(bspec, seq_axis), P()),
        out_specs=P(bspec), check_vma=False)
    return sm(q_c, q_r, ckv_new, krope_new, ckv_c, krope_c, cache_len)


def _prod(mesh, axes):
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n
