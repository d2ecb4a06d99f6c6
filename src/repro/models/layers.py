"""Core layer primitives: init helpers, norms, MLPs, RoPE/M-RoPE, GQA attention.

Everything is a pure function over dict pytrees — no framework dependency.
Shapes use B=batch, S=query length, T=key length, H=heads, K=kv heads, D=head dim.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------


def _normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


def dense_init(key, d_in, d_out, dtype, bias=False, scale=None):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": _normal(key, (d_in, d_out), scale, dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def _against(x, w):
    """The activation operand of a matmul of ``x`` against weight ``w``,
    and the dtype to accumulate in. A weight narrower than the activation
    (bf16 params under an f32 residual stream) takes the activation in its
    own dtype and accumulates in the activation's: the one bf16 MXU pass
    that the TPU's default precision makes of an f32 matmul, without
    converting the weight. Otherwise jnp's promotion (None)."""
    if jnp.dtype(w.dtype).itemsize < jnp.dtype(x.dtype).itemsize:
        return x.astype(w.dtype), x.dtype
    return x, None


def weight_einsum(spec, x, w):
    """``einsum(spec, x, w)`` against a weight, under ``_against``'s rule."""
    x, acc = _against(x, w)
    return jnp.einsum(spec, x, w, preferred_element_type=acc)


def dense(p, x):
    xw, acc = _against(x, p["w"])
    y = jnp.matmul(xw, p["w"], preferred_element_type=acc)
    if "b" in p:
        y = y + p["b"]
    return y


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_init(dim, dtype):
    return {"scale": jnp.ones((dim,), dtype)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rmsnorm_core(scale, x, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(x.dtype)
    return x * inv * scale.astype(x.dtype)


def _rmsnorm_fwd(scale, x, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)                       # f32 (..., 1)
    y = x * inv.astype(x.dtype) * scale.astype(x.dtype)
    return y, (scale, x, inv)


def _rmsnorm_bwd(eps, res, dy):
    # Custom VJP so the residual is (x bf16, inv f32[...,1]) — plain AD of
    # square(x.astype(f32)) saves the f32 UPCAST of x, which XLA then hoists
    # into the layer-scan residual stack: every layer input stored twice
    # (bf16 + f32; measured +6.4 GB/device on grok-1 train_4k).
    scale, x, inv = res
    xf = x.astype(jnp.float32)
    g = dy.astype(jnp.float32) * scale.astype(jnp.float32)
    proj = jnp.mean(g * xf, axis=-1, keepdims=True)
    dx = inv * g - xf * (inv ** 3) * proj
    dscale = jnp.sum(dy.astype(jnp.float32) * xf * inv,
                     axis=tuple(range(x.ndim - 1)))
    return dscale.astype(scale.dtype), dx.astype(x.dtype)


_rmsnorm_core.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm(p, x, eps=1e-6):
    return _rmsnorm_core(p["scale"], x, eps)


def gated_rmsnorm(p, x, z, eps=1e-6):
    """Mamba2-style gated norm: rmsnorm(x * silu(z))."""
    return rmsnorm(p, x * jax.nn.silu(z), eps)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_init(key, cfg, d_ff=None, dtype=None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    dtype = dtype or jnp.dtype(cfg.dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w1": dense_init(k1, d, ff, dtype),
         "w2": dense_init(k2, ff, d, dtype)}
    if cfg.gated_mlp:
        p["w3"] = dense_init(k3, d, ff, dtype)
    return p


def mlp(cfg, p, x):
    act = jax.nn.silu if cfg.mlp_act == "silu" else jax.nn.gelu
    h = act(dense(p["w1"], x))
    if cfg.gated_mlp:
        h = h * dense(p["w3"], x)
    return dense(p["w2"], h)


# ---------------------------------------------------------------------------
# RoPE (standard + M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim, theta):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def yarn_mscale(scale, mscale):
    """Yarn's magnitude factor 0.1 * mscale * ln(scale) + 1 (1 when the
    context is not stretched)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(head_dim, theta, factor, beta_fast, beta_slow,
               original_max_pos):
    """Yarn's inverse frequencies (DeepSeek-V2's rope_scaling "yarn"):
    theta's own below the correction range, theta's divided by ``factor``
    above it, blended by a linear ramp over the range. The range's ends are
    the dims whose wavelength turns ``beta_fast`` and ``beta_slow`` times
    over ``original_max_pos`` positions."""
    def dim_at(rotations):
        return (head_dim * math.log(original_max_pos
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_at(beta_fast)), 0)
    high = min(math.ceil(dim_at(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    extrapolated = rope_freqs(head_dim, theta)
    return (extrapolated / factor * ramp
            + extrapolated * (1 - ramp)).astype(np.float32)


def apply_rope(x, positions, theta, inv_freq=None, scale=1.0):
    """x: (B, S, H, D); positions: (B, S) int32. ``inv_freq`` (D/2,) in
    place of theta's, and cos and sin times ``scale``, for scaled ropes."""
    D = x.shape[-1]
    inv = jnp.asarray(rope_freqs(D, theta) if inv_freq is None
                      else inv_freq)                     # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * inv  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions, theta, sections):
    """M-RoPE (Qwen2-VL): positions (3, B, S) for t/h/w; ``sections`` partitions
    the D/2 frequency slots among the three position streams."""
    D = x.shape[-1]
    inv = jnp.asarray(rope_freqs(D, theta))             # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * inv  # (3, B, S, D/2)
    # select which position stream (t/h/w) drives each frequency slot
    sec_id = np.repeat(np.arange(3), np.asarray(sections))   # (D/2,)
    onehot = jax.nn.one_hot(jnp.asarray(sec_id), 3, dtype=jnp.float32)  # (D/2, 3)
    angles = jnp.einsum("tbsd,dt->bsd", angles, onehot)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len, d_model):
    pos = np.arange(seq_len)[:, None]
    dim = np.arange(0, d_model, 2)[None, :]
    ang = pos / (10000 ** (dim / d_model))
    out = np.zeros((seq_len, d_model), np.float32)
    out[:, 0::2] = np.sin(ang)
    out[:, 1::2] = np.cos(ang)
    return jnp.asarray(out)


# ---------------------------------------------------------------------------
# Attention (GQA reference path; Pallas kernels live in repro.kernels)
# ---------------------------------------------------------------------------


def attention_init(key, cfg, dtype=None):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], d, cfg.n_heads * hd, dtype, bias=cfg.attn_bias),
         "wk": dense_init(ks[1], d, cfg.n_kv_heads * hd, dtype, bias=cfg.attn_bias),
         "wv": dense_init(ks[2], d, cfg.n_kv_heads * hd, dtype, bias=cfg.attn_bias),
         "wo": dense_init(ks[3], cfg.n_heads * hd, d, dtype)}
    if cfg.qk_norm:
        p["q_norm"] = rmsnorm_init(hd, dtype)
        p["k_norm"] = rmsnorm_init(hd, dtype)
    return p


def gqa_scores_softmax_out(q, k, v, mask, scale):
    """q: (B,S,Hq,D) k,v: (B,T,Hkv,D[v]), mask: broadcastable (B,1,1,S,T) or None.

    Returns (B,S,Hq,Dv). Softmax in f32. Pure-jnp reference path (the Pallas
    flash kernels in repro.kernels implement the same contract).
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgst,btkv->bskgv", probs.astype(v.dtype), v)
    return out.reshape(B, S, Hq, v.shape[-1])


def causal_mask(S, T, offset):
    """Query i (global pos offset+i) may attend key j iff j <= offset + i."""
    i = jnp.arange(S)[:, None]
    j = jnp.arange(T)[None, :]
    return (j <= (i + offset))[None, None, None, :, :]


def attention(cfg, p, x, positions, *, mask_offset=0, kv_cache=None,
              cache_len=None, mrope_positions=None):
    """Full attention for train/prefill (kv_cache None) or decode (kv_cache set).

    kv_cache: dict {"k": (B, Smax, Hkv, D), "v": ...} — decode writes the new
    token at position ``cache_len`` and attends to [0, cache_len].
    Returns (out, new_kv) where new_kv is the (k, v) of this call's tokens for
    cache construction (prefill) or the updated cache (decode).
    """
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope_kind == "standard":
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope_kind == "mrope":
        q = apply_mrope(q, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, cfg.rope_theta, cfg.mrope_sections)
    scale = hd ** -0.5

    if kv_cache is None:  # train / prefill: causal over own tokens
        mask = causal_mask(S, S, mask_offset)
        out = gqa_scores_softmax_out(q, k, v, mask, scale)
        new_kv = {"k": k, "v": v}
    else:  # decode: S == 1
        kc = jax.lax.dynamic_update_slice(
            kv_cache["k"], k, (0, cache_len, 0, 0))
        vc = jax.lax.dynamic_update_slice(
            kv_cache["v"], v, (0, cache_len, 0, 0))
        T = kc.shape[1]
        mask = (jnp.arange(T)[None, :] <= cache_len)[None, None, None, None, :]
        out = gqa_scores_softmax_out(q, kc, vc, mask, scale)
        new_kv = {"k": kc, "v": vc}
    return dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd)), new_kv


def cross_attention_init(key, cfg, dtype=None):
    return attention_init(key, cfg, dtype)


def cross_attention(cfg, p, x, enc_out):
    """Decoder cross-attention over encoder outputs (no mask, no rope)."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = dense(p["wk"], enc_out).reshape(B, enc_out.shape[1], cfg.n_kv_heads, hd)
    v = dense(p["wv"], enc_out).reshape(B, enc_out.shape[1], cfg.n_kv_heads, hd)
    out = gqa_scores_softmax_out(q, k, v, None, hd ** -0.5)
    return dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd))
