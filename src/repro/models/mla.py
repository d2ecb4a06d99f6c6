"""Multi-head Latent Attention (DeepSeek-V2): params and projections.

The attention itself is ``model.mla_fullseq`` (train/prefill: expand the
compressed c_kv back to per-head K/V) and ``model.mla_decode`` (absorb W_uk
into the query and attend directly over the compressed cache (c_kv ‖
k_rope) — per-token cost O(T·(r + d_rope)·H) instead of
O(T·(d_nope+d_rope)·H + T·r·H·d), the trick that makes MLA serve-efficient).
The cache stores only (c_kv: r, k_rope: d_rope) per token (576 for V2-Lite).
Under yarn (``rope_kind`` "yarn", V2-Lite's rope_scaling) the rope heads take
yarn's frequencies and the softmax its temperature (``softmax_scale``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import (dense, dense_init, rmsnorm, rmsnorm_init,
                                 apply_rope, yarn_freqs, yarn_mscale)


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


def rope(cfg, x, positions):
    """The rope of the query and key rope heads, x (B, S, H, d_rope)."""
    if cfg.rope_kind != "yarn":
        return apply_rope(x, positions, cfg.rope_theta)
    inv = yarn_freqs(x.shape[-1], cfg.rope_theta, cfg.rope_factor,
                     cfg.yarn_beta_fast, cfg.yarn_beta_slow,
                     cfg.yarn_original_max_pos)
    scale = (yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
             / yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim))
    return apply_rope(x, positions, cfg.rope_theta, inv_freq=inv, scale=scale)


def softmax_scale(cfg):
    """(d_nope + d_rope)^-0.5, times yarn's mscale(factor, mscale_all_dim)^2
    under yarn."""
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.rope_kind == "yarn" and cfg.yarn_mscale_all_dim:
        scale *= yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim) ** 2
    return scale


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
    krope = rope(cfg, krope, positions)
    return ckv, krope
