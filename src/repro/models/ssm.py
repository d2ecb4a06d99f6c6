"""Mamba2 (SSD — state-space duality) block: chunked scan + single-step decode.

Follows the ssd_minimal_discrete formulation of arXiv:2405.21060 with the
inter-chunk recurrence as a ``lax.scan`` (O(n_chunks), required for the 500k
long-context shape) instead of the quadratic chunk-segsum of the minimal code.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import (dense, dense_init, gated_rmsnorm,
                                 rmsnorm_init, weight_einsum)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def ssm_init(key, cfg, dtype=None):
    d = cfg.d_model
    di = cfg.d_inner_ssm
    H = cfg.n_ssm_heads
    G, N, W = cfg.ssm_n_groups, cfg.ssm_state, cfg.ssm_conv
    dtype = dtype or jnp.dtype(cfg.dtype)
    conv_ch = di + 2 * G * N
    ks = jax.random.split(key, 4)
    return {
        # in_proj -> [z (di), xBC (di + 2GN), dt (H)]
        "in_proj": dense_init(ks[0], d, 2 * di + 2 * G * N + H, dtype),
        "conv_w": (jax.random.normal(ks[1], (W, conv_ch), jnp.float32)
                   * (W ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype),
        "A_log": jnp.zeros((H,), jnp.float32),           # A = -exp(A_log) = -1
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm": rmsnorm_init(di, dtype),
        "out_proj": dense_init(ks[3], di, d, dtype),
    }


# ---------------------------------------------------------------------------
# Pieces
# ---------------------------------------------------------------------------


def _split_in_proj(cfg, zxbcdt):
    di = cfg.d_inner_ssm
    G, N, H = cfg.ssm_n_groups, cfg.ssm_state, cfg.n_ssm_heads
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:2 * di + 2 * G * N]
    dt = zxbcdt[..., 2 * di + 2 * G * N:]
    return z, xBC, dt


def causal_conv(p, xBC):
    """Depthwise causal conv1d over (B, S, C)."""
    W = p["conv_w"].shape[0]
    x = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    # depthwise: sum over the window of shifted slices (W is tiny, 4)
    S = xBC.shape[1]
    out = sum(x[:, i:i + S, :] * p["conv_w"][i][None, None, :] for i in range(W))
    return jax.nn.silu(out + p["conv_b"])


def _segsum(x):
    """x: (..., Q) -> (..., Q, Q) with out[i, j] = sum_{k=j+1..i} x[k], -inf j>i."""
    c = jnp.cumsum(x, axis=-1)
    L = c[..., :, None] - c[..., None, :]
    Q = x.shape[-1]
    mask = jnp.tril(jnp.ones((Q, Q), bool))
    return jnp.where(mask, L, -jnp.inf)


def ssd_chunked(xdt, dA, Bm, Cm, chunk, h0=None):
    """SSD over a full sequence.

    xdt: (B, S, H, P)  — inputs pre-multiplied by dt
    dA : (B, S, H)     — log decay per step (dt * A, A negative)
    Bm, Cm: (B, S, G, N) with G | H (broadcast groups)
    Returns (y (B,S,H,P), h_final (B,H,P,N)).
    """
    B, S, H, P = xdt.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    nc = S // chunk
    assert nc * chunk == S, (S, chunk)

    def c(t):  # (B, S, ...) -> (B, nc, chunk, ...)
        return t.reshape((B, nc, chunk) + t.shape[2:])

    x_, a_, b_, c_ = c(xdt), c(dA), c(Bm), c(Cm)
    b_ = jnp.repeat(b_, rep, axis=3)                  # (B,nc,Q,H,N)
    c_ = jnp.repeat(c_, rep, axis=3)
    a_ = jnp.moveaxis(a_, -1, 2)                       # (B,nc,H,Q)
    a_cum = jnp.cumsum(a_, axis=-1)                    # (B,nc,H,Q)

    # 1. intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(a_.astype(jnp.float32)))       # (B,nc,H,Q,Q)
    scores = jnp.einsum("bcqhn,bcshn->bchqs", c_, b_).astype(jnp.float32)
    y_diag = jnp.einsum("bchqs,bchqs,bcshp->bcqhp",
                        scores, L, x_.astype(jnp.float32))

    # 2. per-chunk final states
    decay_to_end = jnp.exp(a_cum[..., -1:] - a_cum).astype(jnp.float32)  # (B,nc,H,Q)
    states = jnp.einsum("bcqhn,bchq,bcqhp->bchpn",
                        b_.astype(jnp.float32), decay_to_end, x_.astype(jnp.float32))

    # 3. inter-chunk recurrence (scan over chunks)
    chunk_decay = jnp.exp(a_cum[..., -1].astype(jnp.float32))            # (B,nc,H)
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N), jnp.float32)

    def step(h, inp):
        st, dec = inp
        h_new = h * dec[:, :, None, None] + st
        return h_new, h  # emit state *entering* the chunk

    h_final, h_prev = jax.lax.scan(
        step, h0, (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                # (B,nc,H,P,N)

    # 4. contribution of entering state to each position
    state_decay = jnp.exp(a_cum).astype(jnp.float32)   # (B,nc,H,Q)
    y_off = jnp.einsum("bcqhn,bchpn,bchq->bcqhp",
                       c_.astype(jnp.float32), h_prev, state_decay)

    y = (y_diag + y_off).reshape(B, S, H, P)
    return y.astype(xdt.dtype), h_final


def ssm_forward(cfg, p, x, state=None, conv_state=None):
    """Full-sequence (forward/prefill) Mamba2 block. Returns
    (y, (h, conv_state))."""
    B, S, _ = x.shape
    H, P = cfg.n_ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_n_groups, cfg.ssm_state
    di = cfg.d_inner_ssm
    z, xBC_raw, dt = _split_in_proj(cfg, dense(p["in_proj"], x))
    xBC = causal_conv(p, xBC_raw)
    xs = xBC[..., :di].reshape(B, S, H, P)
    Bm = xBC[..., di:di + G * N].reshape(B, S, G, N)
    Cm = xBC[..., di + G * N:].reshape(B, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])           # (B,S,H)
    A = -jnp.exp(p["A_log"])                                              # (H,)
    dA = dt * A                                                           # (B,S,H)
    xdt = xs * dt[..., None].astype(xs.dtype)
    y, h = ssd_chunked(xdt, dA, Bm, Cm, min(cfg.ssm_chunk, S), h0=state)
    y = y + xs * p["D"][None, None, :, None].astype(xs.dtype)
    y = y.reshape(B, S, di)
    y = gated_rmsnorm(p["norm"], y, z, cfg.norm_eps)
    # conv state for subsequent decode = last W-1 *pre-conv* inputs
    W = cfg.ssm_conv
    pad = jnp.pad(xBC_raw, ((0, 0), (max(W - 1 - S, 0), 0), (0, 0)))
    new_conv_state = pad[:, -(W - 1):, :]
    return dense(p["out_proj"], y), (h, new_conv_state)


def ssm_decode(cfg, p, x, state, conv_state):
    """Single-token decode. state: (B,H,P,N) f32; conv_state: (B, W-1, C)."""
    B, S, _ = x.shape  # S == 1
    H, P = cfg.n_ssm_heads, cfg.ssm_head_dim
    G, N = cfg.ssm_n_groups, cfg.ssm_state
    di = cfg.d_inner_ssm
    W = cfg.ssm_conv
    z, xBC, dt = _split_in_proj(cfg, dense(p["in_proj"], x))
    # conv over (conv_state ++ xBC), in the cache's dtype: the window's
    # tail is the next conv state
    window = jnp.concatenate([conv_state, xBC.astype(conv_state.dtype)],
                             axis=1)                          # (B, W, C)
    conv = weight_einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
    xBC = jax.nn.silu(conv)[:, None, :]
    new_conv_state = window[:, 1:, :]
    xs = xBC[..., :di].reshape(B, H, P)
    Bm = xBC[..., di:di + G * N].reshape(B, G, N)
    Cm = xBC[..., di + G * N:].reshape(B, G, N)
    rep = H // G
    Bm = jnp.repeat(Bm, rep, axis=1)                          # (B,H,N)
    Cm = jnp.repeat(Cm, rep, axis=1)
    dt = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + p["dt_bias"])  # (B,H)
    A = -jnp.exp(p["A_log"])
    dec = jnp.exp(dt * A)                                     # (B,H)
    upd = jnp.einsum("bh,bhn,bhp->bhpn", dt, Bm.astype(jnp.float32),
                     xs.astype(jnp.float32))
    h = state * dec[..., None, None] + upd                    # (B,H,P,N)
    y = jnp.einsum("bhn,bhpn->bhp", Cm.astype(jnp.float32), h)
    y = y.astype(xs.dtype) + xs * p["D"][None, :, None].astype(xs.dtype)
    y = y.reshape(B, 1, di)
    y = gated_rmsnorm(p["norm"], y, z, cfg.norm_eps)
    return dense(p["out_proj"], y), (h, new_conv_state)
