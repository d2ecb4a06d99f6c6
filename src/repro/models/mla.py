"""Multi-head Latent Attention (DeepSeek-V2): params and projections.

The attention itself is ``model.mla_fullseq`` (train/prefill: expand the
compressed c_kv back to per-head K/V) and ``model.mla_decode`` (absorb W_uk
into the query and attend directly over the compressed cache (c_kv ‖
k_rope) — per-token cost O(T·(r + d_rope)·H) instead of
O(T·(d_nope+d_rope)·H + T·r·H·d), the trick that makes MLA serve-efficient).
The cache stores only (c_kv: r, k_rope: d_rope) per token (576 for V2-Lite).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import (dense, dense_init, rmsnorm, rmsnorm_init,
                                 apply_rope)


def mla_init(key, cfg, dtype=None):
    d = cfg.d_model
    H = cfg.n_heads
    nope, rope_d, vd, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                           cfg.v_head_dim, cfg.kv_lora_rank)
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 6)
    p = {
        "wq": dense_init(ks[0], d, H * (nope + rope_d), dtype),
        "wdkv": dense_init(ks[1], d, r + rope_d, dtype),
        "ckv_norm": rmsnorm_init(r, dtype),
        "wuk": (jax.random.normal(ks[2], (r, H, nope), jnp.float32)
                * (r ** -0.5)).astype(dtype),
        "wuv": (jax.random.normal(ks[3], (r, H, vd), jnp.float32)
                * (r ** -0.5)).astype(dtype),
        "wo": dense_init(ks[4], H * vd, d, dtype),
    }
    return p


def _project_q(cfg, p, x):
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q = dense(p["wq"], x).reshape(B, S, H, nope + rope_d)
    return q[..., :nope], q[..., nope:]


def _project_ckv(cfg, p, x, positions):
    """Returns (c_kv normalized (B,S,r), k_rope roped (B,S,1,rope_d))."""
    r, rope_d = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dkv = dense(p["wdkv"], x)
    ckv = rmsnorm(p["ckv_norm"], dkv[..., :r], cfg.norm_eps)
    krope = dkv[..., None, r:]  # single shared rope head
    krope = apply_rope(krope, positions, cfg.rope_theta)
    return ckv, krope
