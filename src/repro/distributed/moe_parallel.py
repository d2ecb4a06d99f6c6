"""Expert-parallel MoE via shard_map + all-to-all.

Dispatch pattern (DeepSeek-class thin-expert MoE; E % ep == 0):

  1. each device routes its LOCAL tokens (router replicated, f32),
  2. scatters them into an (E, C, d) capacity buffer (sort-free, near-zero
     FLOPs — unlike GShard's one-hot einsum dispatch whose FLOPs rival the
     expert matmuls when experts are thin),
  3. all-to-all over the EP axis: (ep, E_local, C, d) -> each device now
     holds the tokens of ITS E_local experts from every peer,
  4. batched expert FFN (E_local, ep*C, d),
  5. reverse all-to-all + gather + weighted combine.

Differentiable end-to-end (all_to_all and scatters have transposes), so the
same path serves train and prefill. Capacity overflow drops tokens onto the
residual stream (standard capacity-factor semantics).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import moe as Moe
from repro.models.layers import mlp


def moe_ffn_ep(cfg, p, x, *, mesh, ep_axis="model", batch_axes=("data",)):
    """x: (B,S,d) -> (y, aux).

    Requires cfg.n_experts % ep == 0 and S % ep == 0: tokens are
    sequence-split over the EP axis (each EP peer routes a disjoint token
    shard — the DeepSeek-EP layout), so the all-to-all carries real traffic
    instead of replicated work.
    """
    E, K = cfg.n_experts, cfg.experts_per_tok
    ep = mesh.shape[ep_axis]
    assert E % ep == 0, (E, ep)
    B, S, d = x.shape
    assert S % ep == 0, (S, ep)
    E_local = E // ep
    b_axes = tuple(a for a in batch_axes if a in mesh.axis_names)
    nb = 1
    for a in b_axes:
        nb *= mesh.shape[a]
    bspec = b_axes if len(b_axes) > 1 else (b_axes[0] if b_axes else None)
    if B % max(nb, 1) != 0:
        bspec = None

    def local(x, router, experts, shared):
        Bl, Sl = x.shape[:2]
        T = Bl * Sl
        x2d = x.reshape(T, d)
        w, idx, probs = Moe.route(cfg, {"router": router}, x2d)
        slot, valid, C = Moe.dispatch_slots(cfg, idx, T)
        xk = jnp.repeat(x2d, K, axis=0)
        buf = jnp.zeros((E * C, d), x.dtype).at[slot].set(
            xk * valid[:, None].astype(x.dtype), mode="drop")
        buf = buf.reshape(ep, E_local * C, d)
        # dispatch: send chunk i to peer i (tokens for ITS experts)
        buf = jax.lax.all_to_all(buf, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        # buf: (ep, E_local*C, d) — rows grouped by (expert, src-dev capacity)
        buf = jnp.moveaxis(buf.reshape(ep, E_local, C, d), 0, 1)
        buf = buf.reshape(E_local, ep * C, d)
        out = Moe.expert_ffn(cfg, experts, buf)             # (E_local,ep*C,d)
        out = jnp.moveaxis(out.reshape(E_local, ep, C, d), 1, 0)
        out = out.reshape(ep, E_local * C, d)
        out = jax.lax.all_to_all(out, ep_axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        out = out.reshape(E * C, d)
        yk = out.at[slot].get(mode="fill", fill_value=0)
        yk = yk * valid[:, None].astype(x.dtype)
        y = jnp.sum(yk.reshape(T, K, d) * w[..., None].astype(x.dtype),
                    axis=1)
        if cfg.n_shared_experts:
            y = y + mlp(cfg, shared, x2d)
        # load-balance aux from GLOBAL statistics: pmean the per-expert
        # mean-prob and assignment-fraction first, THEN take the product —
        # the product of local means != mean of local products.
        me = jnp.mean(probs, axis=0)                             # (E,)
        fe = jnp.mean(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                      axis=(0, 1))
        for a in b_axes + (ep_axis,):
            me = jax.lax.pmean(me, a)
            fe = jax.lax.pmean(fe, a)
        aux = E * jnp.sum(me * fe)
        return y.reshape(Bl, Sl, d), aux

    shared = p.get("shared")
    if shared is None:
        shared = {"w1": {"w": jnp.zeros((0,), x.dtype)},
                  "w2": {"w": jnp.zeros((0,), x.dtype)},
                  "w3": {"w": jnp.zeros((0,), x.dtype)}}
    sm = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(bspec, ep_axis), P(), P(ep_axis), P()),
        out_specs=(P(bspec, ep_axis), P()),
        check_vma=False)
    y, aux = sm(x, p["router"], p["experts"], shared)
    return y, aux
