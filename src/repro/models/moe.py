"""Mixture-of-Experts FFN with capacity-bounded sort-free dispatch.

The local path (this file) computes exact top-k routing with a per-call token
capacity: tokens are scattered into an (E, C, d) buffer by (expert, rank)
slot, experts run as one batched matmul, and results are gathered back and
combined with the router weights (renormalized over the top k unless
``cfg.norm_topk_prob`` is off). Overflowing tokens are dropped (standard
capacity-factor semantics) — the residual stream carries them.

``moe_ffn_dropless`` is the serving layer: it drops nothing, and holds only
the chip's share of the routed experts (``cfg.n_experts_held`` from
``cfg.expert_offset``) while routing over all ``cfg.n_experts``.

Distributed variants (expert-parallel all-to-all via shard_map) live in
``repro.distributed.moe_parallel``; they reuse these param layouts.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, mlp, mlp_init, weight_einsum


def moe_init(key, cfg, dtype=None):
    d, ffe, E = cfg.d_model, cfg.d_ff_expert, cfg.n_experts
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 5)
    lo, n = cfg.expert_offset, cfg.experts_held

    def ew(k, a, b):
        # a share is sliced from the whole layer's draw, so it holds the very
        # experts the whole layer has at its positions
        w = jax.random.normal(k, (E, a, b), jnp.float32)[lo:lo + n]
        return (w * (a ** -0.5)).astype(dtype)

    p = {
        "router": dense_init(ks[0], d, E, jnp.float32),  # router kept in f32
        "experts": {"w1": ew(ks[1], d, ffe), "w3": ew(ks[2], d, ffe),
                    "w2": ew(ks[3], ffe, d)},
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_init(ks[4], cfg, d_ff=cfg.n_shared_experts * ffe, dtype=dtype)
    return p


def route(cfg, p, x2d):
    """x2d: (T, d) -> (weights (T,K), idx (T,K), router probs for aux loss).

    The matmul keeps x2d in compute dtype with f32 ACCUMULATION
    (preferred_element_type) instead of upcasting x2d — an f32 copy of the
    full activation would be saved for the router backward on every layer
    (XLA hoists it into the scan residual stack; measured GBs/device).
    """
    w_r = p["router"]["w"].astype(x2d.dtype)
    logits = jax.lax.dot_general(
        x2d, w_r, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return _top_k(cfg, logits)


def _top_k(cfg, logits):
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, cfg.experts_per_tok)
    if cfg.norm_topk_prob:
        w = w / (w.sum(-1, keepdims=True) + 1e-9)
    if cfg.routed_scaling_factor != 1.0:
        w = w * cfg.routed_scaling_factor
    return w, idx, probs


def load_balance_loss(cfg, probs, idx):
    """Switch-style aux loss: E * sum_e(f_e * p_e)."""
    E = cfg.n_experts
    me = jnp.mean(probs, axis=0)                                  # (E,)
    fe = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=(0, 1))
    return E * jnp.sum(me * fe)


def capacity(cfg, n_tokens):
    c = int(n_tokens * cfg.experts_per_tok / cfg.n_experts * cfg.moe_capacity_factor)
    return max(c, 8)


def dispatch_slots(cfg, idx, n_tokens):
    """Compute (slot, valid) for each (token, k) assignment.

    slot = expert_id * C + rank_within_expert; overflow gets an out-of-range
    slot so scatter/gather with mode='drop'/'fill' handles it.
    """
    E, K = cfg.n_experts, cfg.experts_per_tok
    C = capacity(cfg, n_tokens)
    flat_e = idx.reshape(-1)                                      # (T*K,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (T*K, E)
    rank = jnp.cumsum(onehot, axis=0) - onehot                    # exclusive
    rank = jnp.take_along_axis(rank, flat_e[:, None], axis=1)[:, 0]
    valid = rank < C
    slot = jnp.where(valid, flat_e * C + rank, E * C)             # E*C = drop
    return slot, valid, C


def expert_ffn(cfg, experts, buf):
    """buf: (E, C, d) -> (E, C, d) through gated-SiLU expert MLPs."""
    h1 = jax.nn.silu(weight_einsum("ecd,edf->ecf", buf, experts["w1"]))
    h3 = weight_einsum("ecd,edf->ecf", buf, experts["w3"])
    return weight_einsum("ecf,efd->ecd", h1 * h3, experts["w2"])


def _whole(cfg):
    if cfg.experts_held != cfg.n_experts:
        raise ValueError(f"{cfg.name}: holds {cfg.experts_held} of "
                         f"{cfg.n_experts} experts; only the dropless layer "
                         "computes a share")


def moe_ffn(cfg, p, x):
    """x: (B, S, d) -> (y, aux_loss). Exact top-k with capacity drop."""
    _whole(cfg)
    B, S, d = x.shape
    T = B * S
    x2d = x.reshape(T, d)
    w, idx, probs = route(cfg, p, x2d)
    slot, valid, C = dispatch_slots(cfg, idx, T)
    E, K = cfg.n_experts, cfg.experts_per_tok

    xk = jnp.repeat(x2d, K, axis=0)                               # (T*K, d)
    buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(
        xk * valid[:, None].astype(x.dtype), mode="drop")
    out = expert_ffn(cfg, p["experts"], buf.reshape(E, C, d)).reshape(E * C, d)
    yk = out.at[slot].get(mode="fill", fill_value=0)              # (T*K, d)
    yk = yk * valid[:, None].astype(x.dtype)
    y = jnp.sum(yk.reshape(T, K, d) * w[..., None].astype(x.dtype), axis=1)

    if cfg.n_shared_experts:
        y = y + mlp(cfg, p["shared"], x2d)
    return y.reshape(B, S, d), load_balance_loss(cfg, probs, idx)


def moe_ffn_dropless(cfg, p, x):
    """x: (B, S, d) -> (y, routes). The serving layer: the router's
    ``n_experts`` outputs in f32 at full precision (as the published gate
    computes them), its top-k weights, and then each held expert over every
    token, weighted by its gate, which is zero for a token that did not
    route to it. No token is dropped, and no token's output depends on the
    other tokens of the batch. Only the held experts' part of the routed
    result is computed, plus the shared experts. ``routes`` (T, K) are the
    global expert ids each token routed to."""
    B, S, d = x.shape
    T = B * S
    x2d = x.reshape(T, d)
    logits = jnp.matmul(x2d.astype(jnp.float32), p["router"]["w"],
                        precision=jax.lax.Precision.HIGHEST)
    w, idx, _ = _top_k(cfg, logits)
    held = cfg.expert_offset + jnp.arange(cfg.experts_held)
    gate = jnp.sum(jnp.where(idx[:, :, None] == held, w[:, :, None], 0.0),
                   axis=1)                                        # (T, n)
    ex = p["experts"]
    h = jax.nn.silu(weight_einsum("td,edf->etf", x2d, ex["w1"])) * \
        weight_einsum("td,edf->etf", x2d, ex["w3"])
    y = weight_einsum("etf,efd->etd", h, ex["w2"])                # (n, T, d)
    y = jnp.einsum("etd,te->td", y, gate.astype(y.dtype))
    if cfg.n_shared_experts:
        y = y + mlp(cfg, p["shared"], x2d)
    return y.reshape(B, S, d).astype(x.dtype), idx


# ---------------------------------------------------------------------------
# GShard-style grouped einsum dispatch (GSPMD-friendly: all matmuls).
# ---------------------------------------------------------------------------


def combine_tensor(cfg, idx, w, valid, C):
    """(g,K) expert ids + weights -> (g, E, C) combine weights (f32)."""
    E = cfg.n_experts
    # rank of each (token, k) within its expert, computed per group
    flat_e = idx.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
    rank = jnp.cumsum(onehot, axis=0) - onehot
    rank = jnp.take_along_axis(rank, flat_e[:, None], axis=1)[:, 0]
    rank = rank.reshape(idx.shape)                                # (g, K)
    ok = valid & (rank < C)
    oh_e = jax.nn.one_hot(idx, E, dtype=jnp.float32)              # (g,K,E)
    oh_c = jax.nn.one_hot(rank, C, dtype=jnp.float32)             # (g,K,C)
    comb = jnp.einsum("gk,gke,gkc->gec",
                      w * ok.astype(jnp.float32), oh_e, oh_c)
    return comb


def moe_ffn_einsum(cfg, p, x, group_size=2048):
    """GShard-style dispatch: (groups, g, E, C) combine tensors + einsums.

    Shards cleanly under GSPMD (groups follow the token/batch sharding, the
    expert dim or d_ff can be TP-sharded). Preferred when experts are fat
    (grok: d_ff 32768) so dispatch FLOPs amortize; thin-expert models
    (deepseek) use the shard_map EP path in repro.distributed.moe_parallel.
    """
    _whole(cfg)
    B, S, d = x.shape
    T = B * S
    g = min(group_size, T)
    n_groups = T // g
    assert n_groups * g == T, (T, g)
    x2d = x.reshape(T, d)
    w, idx, probs = route(cfg, p, x2d)
    C = capacity(cfg, g)

    def one_group(xg, wg, ig):
        comb = combine_tensor(cfg, ig, wg, jnp.ones(ig.shape, bool), C)
        disp = (comb > 0).astype(xg.dtype)                        # (g,E,C)
        buf = jnp.einsum("gec,gd->ecd", disp, xg)                 # (E,C,d)
        out = expert_ffn(cfg, p["experts"], buf)                  # (E,C,d)
        return jnp.einsum("gec,ecd->gd", comb.astype(xg.dtype), out)

    y = jax.vmap(one_group)(x2d.reshape(n_groups, g, d),
                            w.reshape(n_groups, g, cfg.experts_per_tok),
                            idx.reshape(n_groups, g, cfg.experts_per_tok))
    y = y.reshape(T, d)
    if cfg.n_shared_experts:
        y = y + mlp(cfg, p["shared"], x2d)
    return y.reshape(B, S, d), load_balance_loss(cfg, probs, idx)
