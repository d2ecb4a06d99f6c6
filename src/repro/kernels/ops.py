"""jit'd public wrappers around the Pallas kernels.

``interpret`` defaults to True when no TPU is present (this container), so
the same call sites run the kernel body on CPU for correctness and compile
to Mosaic on a real TPU (interpret=False).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import (combine_splits,
                                            decode_attention_pallas)
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.mips_topk import mips_topk_pallas, select_topk
from repro.kernels.mips_topk_int8 import mips_topk_int8_pallas


def _default_interpret():
    return jax.default_backend() != "tpu"


def _combine_tiles(vals, idx, k):
    """Reduce per-tile candidates (nt, Q, k) to the global top-k under the
    kernels' (value desc, index asc) contract."""
    nt, Q = vals.shape[0], vals.shape[1]
    vflat = jnp.moveaxis(vals, 0, 1).reshape(Q, nt * k)
    iflat = jnp.moveaxis(idx, 0, 1).reshape(Q, nt * k)
    return select_topk(vflat, iflat, k)


@functools.partial(jax.jit, static_argnums=(2, 3))
def mips_topk(q, x, k, tile_n=512):
    """q: (Q,D); x: (N,D) -> exact (vals (Q,k), GLOBAL idx (Q,k)).

    ``tile_n`` is clamped to the (128-aligned) store size so small stores —
    common early in a serving run, before write-backs grow them — don't
    scan a mostly-padded tile; the per-tile top-k needs k <= tile_n.
    """
    n = x.shape[0]
    if k > n:
        raise ValueError(f"k={k} exceeds store rows N={n}")
    tile_n = max(min(tile_n, -(-n // 128) * 128), k)
    vals, idx = mips_topk_pallas(q, x, k, tile_n=tile_n,
                                 interpret=_default_interpret())
    return _combine_tiles(vals, idx, k)


@functools.partial(jax.jit, static_argnums=(4, 5))
def mips_topk_int8(q, q_scale, x, x_scale, k, tile_n=512):
    """Quantized exact-over-the-quantized-grid MIPS: q (Q,D) int8 with
    per-row f32 ``q_scale`` (Q,), x (N,D) int8 with per-row ``x_scale``
    (N,) -> (dequantized vals (Q,k), GLOBAL idx (Q,k)). Same clamping and
    combine as ``mips_topk``; bit-for-bit against ref.mips_topk_int8_ref.
    """
    n = x.shape[0]
    if k > n:
        raise ValueError(f"k={k} exceeds store rows N={n}")
    tile_n = max(min(tile_n, -(-n // 128) * 128), k)
    vals, idx = mips_topk_int8_pallas(q, q_scale, x, x_scale, k,
                                      tile_n=tile_n,
                                      interpret=_default_interpret())
    return _combine_tiles(vals, idx, k)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal=True, q_block=256, kv_block=512):
    """Model layout: q (B,S,Hq,D); k,v (B,T,Hkv,D) -> (B,S,Hq,D)."""
    qt = jnp.transpose(q, (0, 2, 1, 3))
    kt = jnp.transpose(k, (0, 2, 1, 3))
    vt = jnp.transpose(v, (0, 2, 1, 3))
    o = flash_attention_pallas(qt, kt, vt, causal=causal, q_block=q_block,
                               kv_block=kv_block,
                               interpret=_default_interpret())
    return jnp.transpose(o, (0, 2, 1, 3))


@functools.partial(jax.jit, static_argnums=(4,))
def decode_attention(q, k, v, lengths, n_splits=8):
    """q: (B,Hq,D); k,v: (B,T,Hkv,D); lengths (B,) -> (B,Hq,D)."""
    o, m, l = decode_attention_pallas(q, k, v, lengths, n_splits=n_splits,
                                      interpret=_default_interpret())
    return combine_splits(o, m, l)
