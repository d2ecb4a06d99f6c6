"""Distributed top-k for the mesh-sharded MIPS index.

The precomputed-query embedding matrix is row-sharded over the "model" axis;
each device scans its shard (one matmul), takes a local top-k, then an
all-gather of the (k-sized) candidate lists and a final top-k. Traffic per
query: shards * k * 8 bytes — independent of store size N.

Quantized stores shard int8 values + per-row f32 scales (``scales=``): the
local scan scores an int8 query block against the int8 shard with exact
int32 accumulation, then dequantizes in the same order as the one-chip
int8 kernel (acc -> f32, * q_scale, * x_scale), so each device holds and
streams ~1/4 of the fp32 bytes and the sharded tier returns the same
scores as the flat tier. int8 cannot encode the float path's -1e4
padding fill, so padded rows are masked out by global row id instead
(``n_real=``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels.mips_topk import NEG, select_topk


def sharded_mips_topk(queries, emb, k, *, mesh, shard_axis="model",
                      scales=None, n_real=None, q_scale=None):
    """queries: (Q, D) replicated; emb: (N, D) row-sharded over shard_axis.

    Returns (scores (Q, k), indices (Q, k)) — replicated, GLOBAL row ids,
    ordered by (value desc, index asc) like the one-chip kernels.
    ``scales`` (row-sharded (N,) f32) switches to the int8 shard scan,
    which takes int8 ``queries`` with their per-row ``q_scale`` (Q,) f32;
    ``n_real`` masks padded rows (global id >= n_real) before the local
    top-k.
    """

    def local_topk(s, e):
        rows = jax.lax.axis_index(shard_axis) * e.shape[0] \
            + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        if n_real is not None:
            s = jnp.where(rows < n_real, s, NEG)
        v, i = select_topk(s, rows, k)
        vg = jax.lax.all_gather(v, shard_axis, axis=1, tiled=True)
        ig = jax.lax.all_gather(i, shard_axis, axis=1, tiled=True)
        return select_topk(vg, ig, k)

    if scales is not None:
        def local(q, qs, e, sc):
            s = jax.lax.dot_general(q, e, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.int32)
            return local_topk(
                s.astype(jnp.float32) * qs[:, None] * sc[None, :], e)

        sm = jax.shard_map(local, mesh=mesh,
                           in_specs=(P(), P(), P(shard_axis),
                                     P(shard_axis)),
                           out_specs=(P(), P()), check_vma=False)
        return sm(queries, q_scale, emb, scales)

    def local(q, e):
        return local_topk(q.astype(jnp.float32) @ e.T.astype(jnp.float32),
                          e)

    sm = jax.shard_map(local, mesh=mesh, in_specs=(P(), P(shard_axis)),
                       out_specs=(P(), P()), check_vma=False)
    return sm(queries, emb)
