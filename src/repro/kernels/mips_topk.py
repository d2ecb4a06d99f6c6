"""Pallas TPU kernel: tiled MIPS + per-tile top-k (the StorInfer hot spot).

The paper scans a DiskANN graph on CPU; on TPU the same search is a matmul
(DESIGN.md §3): the store shard streams through VMEM in (TILE_N, D) blocks,
each block scoring against the resident query block on the MXU, followed by
an on-chip streaming top-k over the tile (``tile_topk``, shared with the
int8 kernel in mips_topk_int8.py). The host-side combine (ops.py) reduces
the (n_tiles, Q, K) candidates with one final lax.top_k — O(n_tiles * K)
per query, independent of N.

Tiling:
  q   : (Q, D)       resident in VMEM for the whole grid (Q <= ~1024)
  x   : (TILE_N, D)  one store tile per grid step (128-aligned)
  out : (Q, K) vals + (Q, K) idx per tile, written to grid slot i

VMEM working set per step ~= Q*D + TILE_N*D + Q*TILE_N floats; defaults
(Q<=256, TILE_N=512, D=384) ~ 1 MB — far under the ~16 MB v5e VMEM budget;
the MXU sees (Q x D) @ (D x TILE_N) with D padded to a lane multiple of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = -1e30
# the running candidate list starts as (NEG, _IDX_PAD) entries; the index
# must sort after every real (< 2^24) row id under (value desc, index asc)
_IDX_PAD = 2 ** 30


def _ge(av, ai, bv, bi):
    """Strict total order used everywhere in the tile top-k: value
    descending, index ascending on value ties. Matching the numpy
    reference's tie-break exactly is what makes the int8 kernel's
    bit-for-bit validation possible."""
    return (av > bv) | ((av == bv) & (ai <= bi))


def _first_max(v, i):
    """Per row of (Q, M) values ``v`` with indices ``i``: the max and the
    lowest index holding it. Spelled out rather than argmax / lax.top_k,
    whose tie-break neither Mosaic nor XLA on TPU promises."""
    m = jnp.max(v, axis=1)
    return m, jnp.min(jnp.where(v == m[:, None], i, _IDX_PAD), axis=1)


def select_topk(v, i, k):
    """Exact top-k of (Q, M) candidates ``v`` with int32 indices ``i``,
    ordered by (value desc, index asc): k passes of ``_first_max``."""
    vals, idxs = [], []
    for p in range(k):
        m, a = _first_max(v, i)
        vals.append(m)
        idxs.append(a)
        if p + 1 < k:
            v = jnp.where(i == a[:, None], NEG, v)
    return jnp.stack(vals, axis=1), jnp.stack(idxs, axis=1)


def _fold_chunk(rv, ri, s, col0):
    """Top-k of the union of a running candidate list (rv, ri) (Q, k) and
    one (Q, c) score chunk whose first column is ``col0``, emitted in
    (value desc, index asc) order.

    Each of the k passes takes the best of the chunk and the best of the
    list (``_first_max``), keeps the winner under ``_ge`` and masks it out
    of its side. Only lane reductions and element-wise selects: no
    reversal and no reshape of the lane dimension, which Mosaic does not
    lower."""
    k = rv.shape[1]
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    vals, idxs = [], []
    for p in range(k):
        mc, ic = _first_max(s, cols)
        mr, ir = _first_max(rv, ri)
        take = _ge(mr, ir, mc, ic)
        vals.append(jnp.where(take, mr, mc))
        idxs.append(jnp.where(take, ir, ic))
        if p + 1 < k:
            t = take[:, None]
            rv = jnp.where(t & (ri == ir[:, None]), NEG, rv)
            s = jnp.where(~t & (cols == ic[:, None]), NEG, s)
    return jnp.stack(vals, axis=1), jnp.stack(idxs, axis=1)


def tile_topk(s, k, *, chunk=128):
    """Exact top-k along the last axis of ``s`` (Q, T), ordered by
    (value desc, index asc). Returns (vals (Q, k), idx (Q, k) int32).

    The tile is streamed once in lane-width chunks; each chunk is folded
    into a running (Q, k) candidate list inside that small hot block, so
    the (Q, T) score block is read once and never written back.
    """
    Q, T = s.shape
    if k > T:
        raise ValueError(f"tile_topk: k={k} exceeds tile width {T}")
    c = min(chunk, T)
    if T % c:
        c = T                      # ragged tile: single chunk
    rv = jnp.full((Q, k), NEG, s.dtype)
    ri = jnp.full((Q, k), _IDX_PAD, jnp.int32)
    for lo in range(0, T, c):
        rv, ri = _fold_chunk(rv, ri, s[:, lo:lo + c], lo)
    return rv, ri


def _mips_kernel(q_ref, x_ref, vals_ref, idx_ref, *, k, tile_n, n_real):
    i = pl.program_id(0)
    q = q_ref[...]                                    # (Q, D)
    x = x_ref[...]                                    # (TILE_N, D)
    # HIGHEST: full f32 products on the MXU, so scores match an f32
    # reference instead of a bf16-pass approximation
    s = jnp.dot(q, x.T, preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST)        # (Q, TILE_N)
    # mask padded store rows (beyond n_real)
    row_global = i * tile_n + jax.lax.broadcasted_iota(jnp.int32,
                                                       s.shape, 1)
    s = jnp.where(row_global < n_real, s, NEG)
    vals, idx = tile_topk(s, k)
    vals_ref[0] = vals
    idx_ref[0] = idx


def mips_topk_pallas(q, x, k, *, tile_n=512, interpret=True):
    """q: (Q, D) f32; x: (N, D) f32 (v5e cannot load f16 vectors, so
    fp16 stores are upcast once at upload; see core/index.DeviceStore).
    Returns per-tile candidates (vals (nt, Q, k), idx-global (nt, Q, k))."""
    Q, D = q.shape
    N = x.shape[0]
    nt = -(-N // tile_n)
    N_pad = nt * tile_n
    if N_pad != N:
        x = jnp.pad(x, ((0, N_pad - N), (0, 0)))
    Dp = -(-D // 128) * 128                           # lane alignment
    if Dp != D:
        q = jnp.pad(q, ((0, 0), (0, Dp - D)))
        x = jnp.pad(x, ((0, 0), (0, Dp - D)))

    kernel = functools.partial(_mips_kernel, k=k, tile_n=tile_n, n_real=N)
    vals, idx = pl.pallas_call(
        kernel,
        grid=(nt,),
        in_specs=[
            pl.BlockSpec((Q, Dp), lambda i: (0, 0)),        # q resident
            pl.BlockSpec((tile_n, Dp), lambda i: (i, 0)),   # x streamed
        ],
        out_specs=[
            pl.BlockSpec((1, Q, k), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, Q, k), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nt, Q, k), jnp.float32),
            jax.ShapeDtypeStruct((nt, Q, k), jnp.int32),
        ],
        interpret=interpret,
    )(q, x)
    # per-tile local idx -> global row ids
    offs = (jnp.arange(nt, dtype=jnp.int32) * tile_n)[:, None, None]
    return vals, idx + offs
