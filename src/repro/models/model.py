"""Model assembly: init / forward / prefill / decode for every assigned family.

Families: dense (llama / qwen / starcoder), vlm (dense + M-RoPE backbone),
moe (deepseek MLA+MoE, grok GQA+MoE), ssm (mamba2), hybrid (zamba2: mamba
backbone + shared attention block), encdec (whisper backbone).

Conventions
-----------
* Params are dict pytrees; uniform layer stacks are STACKED on a leading L
  axis (init via ``jax.vmap``) and applied with ``lax.scan`` (+ optional
  remat) — constant compile size at any depth. Hybrid (38L, non-uniform) and
  whisper (6+6L) apply their stacked params with a Python loop.
* ``RunCfg`` carries implementation choices (attention schedule, MoE
  dispatch, decode sharding) so the same model code serves smoke tests,
  the 512-device dry-run, and the §Perf hillclimb variants.
* Full-seq attention defaults to the blockwise flash path (never
  materializes S x T); ``naive`` is the small-shape oracle.

Cache layouts (leading dim = layer / invocation):
  GQA   : {"k": (L,B,M,Hkv,Dh), "v": (L,B,M,Hkv,Dh)}
  MLA   : {"ckv": (L,B,M,r), "krope": (L,B,M,dr)}   (compressed; absorbed decode)
  SSM   : {"h": (L,B,H,P,N) f32, "conv": (L,B,W-1,C)}
  hybrid: SSM + {"ak"/"av": (I,B,M,Hkv,Dh)}  I = #shared-attn invocations
  encdec: GQA self + {"xk"/"xv": (L,B,Tenc,H,Dh)} cross (static after prefill)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as Lyr
from repro.models import mla as Mla
from repro.models import moe as Moe
from repro.models import ssm as Ssm
from repro.models.blockwise import blockwise_gqa


# ---------------------------------------------------------------------------
# Run configuration (implementation knobs, not architecture)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RunCfg:
    attn_impl: str = "blockwise"      # naive | blockwise
    schedule: str = "rect"            # rect | tri  (causal block skipping)
    q_block: int = 512
    kv_block: int = 1024
    moe_impl: str = "scatter"         # scatter | einsum | ep | dropless
    moe_group: int = 2048
    remat: bool = True
    scan_layers: bool = True
    decode_attn: str = "naive"        # naive | seq_sharded
    mesh: Any = None                  # jax Mesh for shard_map paths
    ep_axis: str = "model"
    seq_axis: str = "model"
    batch_axes: Tuple[str, ...] = ("data",)
    aux_coef: float = 0.01
    stream_dtype: Any = None          # residual stream dtype (None: the
                                      # params'); serving keeps f32 over
                                      # bf16 params, which every matmul
                                      # then takes in bf16 (Lyr.dense)
    heads_sharded: bool = False       # q-heads TP-shard over "model"
    repeat_kv: bool = False           # Megatron-GQA: kv replicated+repeated
    ssm_chunk: int = 0                # override cfg.ssm_chunk (0 = cfg's);
                                      # SSD chunking is exact at any size —
                                      # this is a memory/compute tile knob
    seq_parallel: bool = False        # Megatron-SP: residual stream sharded
                                      # over ("model", seq) between layers —
                                      # GSPMD derives RS+AG instead of AR
    pin_ssm: bool = False             # pin SSD internals to batch-only
                                      # sharding (stops GSPMD speculative
                                      # seq-sharding -> halo permutes)

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


SMOKE = RunCfg(attn_impl="naive", remat=False, q_block=64, kv_block=64,
               moe_group=64)


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------


def _attn_init(key, cfg, dtype):
    if cfg.use_mla:
        return Mla.mla_init(key, cfg, dtype)
    return Lyr.attention_init(key, cfg, dtype)


def init_block(key, cfg, kind, dtype):
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    if kind == "dense":
        return {"ln1": Lyr.rmsnorm_init(d, dtype),
                "attn": _attn_init(ks[0], cfg, dtype),
                "ln2": Lyr.rmsnorm_init(d, dtype),
                "mlp": Lyr.mlp_init(ks[1], cfg, dtype=dtype)}
    if kind == "moe":
        return {"ln1": Lyr.rmsnorm_init(d, dtype),
                "attn": _attn_init(ks[0], cfg, dtype),
                "ln2": Lyr.rmsnorm_init(d, dtype),
                "moe": Moe.moe_init(ks[1], cfg, dtype)}
    if kind == "moe_dense0":  # deepseek leading dense layer
        return {"ln1": Lyr.rmsnorm_init(d, dtype),
                "attn": _attn_init(ks[0], cfg, dtype),
                "ln2": Lyr.rmsnorm_init(d, dtype),
                "mlp": Lyr.mlp_init(ks[1], cfg, d_ff=cfg.d_ff_dense or cfg.d_ff,
                                    dtype=dtype)}
    if kind == "ssm":
        return {"ln": Lyr.rmsnorm_init(d, dtype),
                "ssm": Ssm.ssm_init(ks[0], cfg, dtype)}
    if kind == "enc":
        return {"ln1": Lyr.rmsnorm_init(d, dtype),
                "attn": Lyr.attention_init(ks[0], cfg, dtype),
                "ln2": Lyr.rmsnorm_init(d, dtype),
                "mlp": Lyr.mlp_init(ks[1], cfg, dtype=dtype)}
    if kind == "dec":
        return {"ln1": Lyr.rmsnorm_init(d, dtype),
                "attn": Lyr.attention_init(ks[0], cfg, dtype),
                "lnx": Lyr.rmsnorm_init(d, dtype),
                "xattn": Lyr.cross_attention_init(ks[1], cfg, dtype),
                "ln2": Lyr.rmsnorm_init(d, dtype),
                "mlp": Lyr.mlp_init(ks[2], cfg, dtype=dtype)}
    raise ValueError(kind)


def _stack_init(key, cfg, kind, n, dtype):
    keys = jax.random.split(key, n)

    def init(k):
        return init_block(k, cfg, kind, dtype)

    if kind == "moe" and cfg.experts_held != cfg.n_experts:
        # each layer draws all its experts and keeps its share: one layer
        # at a time, so no more than one layer's draw is ever resident
        return jax.lax.map(init, keys)
    return jax.vmap(init)(keys)


def main_block_kind(cfg) -> str:
    return {"dense": "dense", "vlm": "dense", "moe": "moe", "ssm": "ssm",
            "hybrid": "ssm", "encdec": "dec"}[cfg.family]


def n_shared_attn(cfg) -> int:
    """# shared-attention invocations in a hybrid stack (layers i%k==0)."""
    k = cfg.hybrid_attn_every
    return -(-cfg.n_layers // k) if k else 0


def init_model(key, cfg, dtype=None):
    dtype = dtype or jnp.dtype(cfg.dtype)
    ks = jax.random.split(key, 8)
    vp = cfg.padded_vocab
    params = {
        "embed": {"w": (jax.random.normal(ks[0], (vp, cfg.d_model),
                                          jnp.float32)
                        * cfg.d_model ** -0.5).astype(dtype)},
        "final_norm": Lyr.rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = Lyr.dense_init(ks[1], cfg.d_model, vp, dtype)
    kind = main_block_kind(cfg)
    n_main = cfg.n_layers - (cfg.first_dense_layers if cfg.family == "moe" else 0)
    params["blocks"] = _stack_init(ks[2], cfg, kind, n_main, dtype)
    if cfg.family == "moe" and cfg.first_dense_layers:
        params["dense0"] = _stack_init(ks[3], cfg, "moe_dense0",
                                       cfg.first_dense_layers, dtype)
    if cfg.family == "hybrid":
        params["shared"] = init_block(ks[4], cfg, "dense", dtype)
    if cfg.is_encoder_decoder:
        params["enc_blocks"] = _stack_init(ks[5], cfg, "enc",
                                           cfg.n_encoder_layers, dtype)
        params["enc_norm"] = Lyr.rmsnorm_init(cfg.d_model, dtype)
    return params


def count_params(params) -> int:
    return sum(p.size for p in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Attention (full sequence): projection + impl dispatch
# ---------------------------------------------------------------------------


def _rope_q_k(cfg, p, q, k, positions, mrope_positions):
    if cfg.qk_norm:
        q = Lyr.rmsnorm(p["q_norm"], q, cfg.norm_eps)
        k = Lyr.rmsnorm(p["k_norm"], k, cfg.norm_eps)
    if cfg.rope_kind == "standard":
        q = Lyr.apply_rope(q, positions, cfg.rope_theta)
        k = Lyr.apply_rope(k, positions, cfg.rope_theta)
    elif cfg.rope_kind == "mrope":
        q = Lyr.apply_mrope(q, mrope_positions, cfg.rope_theta,
                            cfg.mrope_sections)
        k = Lyr.apply_mrope(k, mrope_positions, cfg.rope_theta,
                            cfg.mrope_sections)
    return q, k


def _batch_cb(run):
    """Sharding-constraint callback for blockwise attention tiles: pins the
    batch dim to the batch axes and (when q-heads are TP-sharded) the head
    dim to "model" — with_sharding_constraint treats unlisted dims as
    replicated, so the head dim must be named explicitly or the constraint
    itself would gather head-sharded tiles."""
    if run.mesh is None:
        return None

    def cb(t, bdim, hdim=None):
        spec = [None] * t.ndim
        spec[bdim] = run.batch_axes
        if run.heads_sharded and hdim is not None:
            spec[hdim] = "model"
        return _constrain(t, run, *spec)

    return cb


def gqa_fullseq(cfg, run, p, x, positions, *, mrope_positions=None,
                mask_offset=0, causal=True):
    """Returns (out (B,S,d), kv dict) for train/prefill."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = Lyr.dense(p["wq"], x).reshape(B, S, cfg.n_heads, hd)
    k = Lyr.dense(p["wk"], x).reshape(B, S, cfg.n_kv_heads, hd)
    v = Lyr.dense(p["wv"], x).reshape(B, S, cfg.n_kv_heads, hd)
    q, k = _rope_q_k(cfg, p, q, k, positions, mrope_positions)
    G = cfg.n_heads // cfg.n_kv_heads
    ka, va = k, v
    if run.repeat_kv and G > 1:
        # Megatron-GQA: kv heads replicated over "model"; repeat to full
        # head count so the attention einsums shard cleanly on q-heads.
        ka = jnp.repeat(k, G, axis=2)
        va = jnp.repeat(v, G, axis=2)
    if run.attn_impl == "naive":
        mask = Lyr.causal_mask(S, S, mask_offset) if causal else None
        out = Lyr.gqa_scores_softmax_out(q, ka, va, mask, hd ** -0.5)
    else:
        out = blockwise_gqa(q, ka, va, causal=causal, mask_offset=mask_offset,
                            q_block=run.q_block, kv_block=run.kv_block,
                            schedule=run.schedule, constrain=_batch_cb(run))
    return Lyr.dense(p["wo"], out.reshape(B, S, cfg.n_heads * hd)), \
        {"k": k, "v": v}


def mla_fullseq(cfg, run, p, x, positions, *, mask_offset=0):
    """MLA train/prefill: expand compressed KV per head, blockwise attention.

    Returns (out, {"ckv","krope"}) — the cache stays compressed.
    """
    B, S, _ = x.shape
    H = cfg.n_heads
    nope, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    qn, qr = Mla._project_q(cfg, p, x)
    qr = Mla.rope(cfg, qr, positions)
    ckv, krope = Mla._project_ckv(cfg, p, x, positions)
    kn = Lyr.weight_einsum("bsr,rhn->bshn", ckv, p["wuk"])
    v = Lyr.weight_einsum("bsr,rhv->bshv", ckv, p["wuv"])
    q = jnp.concatenate([qn, qr], axis=-1)                     # (B,S,H,nope+rd)
    kr = jnp.broadcast_to(krope, (B, S, H, rd))
    k = jnp.concatenate([kn, kr], axis=-1)
    if run.attn_impl == "naive":
        mask = Lyr.causal_mask(S, S, mask_offset)
        out = Lyr.gqa_scores_softmax_out(q, k, v, mask,
                                         Mla.softmax_scale(cfg))
    else:
        out = blockwise_gqa(q, k, v, causal=True, mask_offset=mask_offset,
                            q_block=run.q_block, kv_block=run.kv_block,
                            schedule=run.schedule, constrain=_batch_cb(run),
                            scale=Mla.softmax_scale(cfg))
    return Lyr.dense(p["wo"], out.reshape(B, S, H * vd)), \
        {"ckv": ckv, "krope": krope[:, :, 0, :]}


def attn_fullseq(cfg, run, p, x, positions, **kw):
    if cfg.use_mla:
        kw.pop("mrope_positions", None)
        kw.pop("causal", None)
        return mla_fullseq(cfg, run, p, x, positions, **kw)
    return gqa_fullseq(cfg, run, p, x, positions, **kw)


# ---------------------------------------------------------------------------
# Attention (single-token decode)
# ---------------------------------------------------------------------------


def write_rows(cache, rows, idx):
    """Write each leaf of ``rows`` (L,B,1,...) at position idx of the
    stacked cache leaf of the same name (L,B,M,...); other leaves pass.
    Where the cache's M is sharded, the partitioner writes inside each
    shard and moves no part of the cache (``tests/dist_checks.py``)."""
    out = dict(cache)
    for name, row in rows.items():
        c = cache[name]
        out[name] = jax.lax.dynamic_update_slice(
            c, row.astype(c.dtype), (0, 0, idx) + (0,) * (c.ndim - 3))
    return out


def _attend_past_and_own(pv, past, own, vals, own_vals, cache_len, scale):
    """One softmax over the scores of the cached positions before
    cache_len (past, (...,T)) and of the step's own row (own, (...,1)),
    then the probabilities times the values: einsum ``pv`` over the cache
    ``vals`` plus the same over ``own_vals``, summed in f32 and rounded
    once to the cache's dtype."""
    f32 = jnp.float32
    T = past.shape[-1]
    past = jnp.where(jnp.arange(T) < cache_len, past.astype(f32) * scale,
                     -1e30)
    probs = jax.nn.softmax(jnp.concatenate([past, own.astype(f32) * scale],
                                           -1), -1).astype(vals.dtype)
    out = (jnp.einsum(pv, probs[..., :T], vals, preferred_element_type=f32)
           + jnp.einsum(pv, probs[..., T:], own_vals,
                        preferred_element_type=f32))
    return out.astype(vals.dtype)


def gqa_decode(cfg, run, p, x, kc, vc, cache_len, *, mrope_positions=None):
    """x (B,1,d); kc/vc (B,M,Hkv,Dh), read only. Attends over the cached
    positions before cache_len and this step's own key and value, in one
    softmax. Returns (out, k, v): k/v (B,1,Hkv,Dh) the step's row, in the
    cache's dtype, for the caller to write at cache_len."""
    B = x.shape[0]
    hd = cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    positions = jnp.full((B, 1), cache_len, jnp.int32)
    q = Lyr.dense(p["wq"], x).reshape(B, 1, Hq, hd)
    k = Lyr.dense(p["wk"], x).reshape(B, 1, Hkv, hd)
    v = Lyr.dense(p["wv"], x).reshape(B, 1, Hkv, hd)
    if mrope_positions is None and cfg.rope_kind == "mrope":
        mrope_positions = jnp.broadcast_to(positions[None], (3, B, 1))
    q, k = _rope_q_k(cfg, p, q, k, positions, mrope_positions)
    # rounded to the cache's dtype first: the same numbers as a write
    # followed by a read
    k, v = k.astype(kc.dtype), v.astype(vc.dtype)

    if run.decode_attn == "seq_sharded" and run.mesh is not None:
        from repro.distributed.decode_attn import gqa_decode_seq_sharded
        out = gqa_decode_seq_sharded(
            q, k, v, kc, vc, cache_len, mesh=run.mesh,
            seq_axis=run.seq_axis, batch_axes=run.batch_axes)
    else:
        qg = q.reshape(B, Hkv, Hq // Hkv, hd)
        score = "bkgd,btkd->bkgt"
        out = _attend_past_and_own(
            "bkgt,btkv->bkgv", jnp.einsum(score, qg, kc),
            jnp.einsum(score, qg, k), vc, v, cache_len, hd ** -0.5)
    return Lyr.dense(p["wo"], out.reshape(B, 1, Hq * hd)), k, v


def mla_decode(cfg, run, p, x, ckv_c, krope_c, cache_len):
    """Absorbed decode over the compressed cache (B,M,r)/(B,M,dr), read
    only, and this step's own latent row, in one softmax. Returns
    (out, ckv (B,1,r), krope (B,1,dr)): the step's row, in the cache's
    dtype, for the caller to write at cache_len."""
    B = x.shape[0]
    H, vd = cfg.n_heads, cfg.v_head_dim
    positions = jnp.full((B, 1), cache_len, jnp.int32)
    qn, qr = Mla._project_q(cfg, p, x)
    qr = Mla.rope(cfg, qr, positions)
    ckv_new, krope_new = Mla._project_ckv(cfg, p, x, positions)
    ckv_new = ckv_new.astype(ckv_c.dtype)
    krope_new = krope_new[:, :, 0, :].astype(krope_c.dtype)
    q_c = Lyr.weight_einsum("bshn,rhn->bshr", qn, p["wuk"])
    scale = Mla.softmax_scale(cfg)

    if run.decode_attn == "seq_sharded" and run.mesh is not None:
        from repro.distributed.decode_attn import mla_decode_seq_sharded
        out_c = mla_decode_seq_sharded(
            q_c, qr, ckv_new, krope_new, ckv_c, krope_c,
            cache_len, scale, mesh=run.mesh, seq_axis=run.seq_axis,
            batch_axes=run.batch_axes)
    else:
        score = "bshr,btr->bhst"

        def scores(ckv, krope):
            return (jnp.einsum(score, q_c, ckv)
                    + jnp.einsum(score, qr, krope))

        # the value dot in its own output order (b,h,s,r): XLA's CPU
        # backend has no bf16 dot with an f32 result in another order
        out_c = jnp.moveaxis(_attend_past_and_own(
            "bhst,btr->bhsr", scores(ckv_c, krope_c),
            scores(ckv_new, krope_new), ckv_c, ckv_new, cache_len, scale),
            1, 2)
    out = Lyr.weight_einsum("bshr,rhv->bshv", out_c, p["wuv"])
    return Lyr.dense(p["wo"], out.reshape(B, 1, H * vd)), ckv_new, krope_new


# ---------------------------------------------------------------------------
# FFN dispatch
# ---------------------------------------------------------------------------


def apply_moe(cfg, run, p, x):
    """x (B, S, d) -> (y, aux_loss, routes): ``routes`` (B*S, K), the
    experts each token routed to, from the dropless serving layer, else
    None."""
    if run.moe_impl == "dropless":
        y, routes = Moe.moe_ffn_dropless(cfg, p, x)
        return y, jnp.zeros((), jnp.float32), routes
    if run.moe_impl == "einsum":
        y, aux = Moe.moe_ffn_einsum(cfg, p, x, run.moe_group)
    elif run.moe_impl == "ep":
        from repro.distributed.moe_parallel import moe_ffn_ep
        y, aux = moe_ffn_ep(cfg, p, x, mesh=run.mesh, ep_axis=run.ep_axis,
                            batch_axes=run.batch_axes)
    else:
        y, aux = Moe.moe_ffn(cfg, p, x)
    return y, aux, None


# ---------------------------------------------------------------------------
# Blocks (full sequence)
# ---------------------------------------------------------------------------


def block_fullseq(cfg, run, p, x, positions, *, kind, mrope_positions=None,
                  enc_out=None, mask_offset=0):
    """One layer. Returns (x, aux_loss, kv_dict_or_None)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("dense", "moe", "moe_dense0", "enc", "dec"):
        h, kv = attn_fullseq(cfg, run, p["attn"], Lyr.rmsnorm(p["ln1"], x,
                                                              cfg.norm_eps),
                             positions, mrope_positions=mrope_positions,
                             mask_offset=mask_offset,
                             causal=(kind != "enc"))
        x = x + h
        if kind == "dec":
            B, S = x.shape[:2]
            Te = enc_out.shape[1]
            hd = cfg.resolved_head_dim
            xq = Lyr.rmsnorm(p["lnx"], x, cfg.norm_eps)
            q = Lyr.dense(p["xattn"]["wq"], xq).reshape(B, S, cfg.n_heads, hd)
            xk = Lyr.dense(p["xattn"]["wk"], enc_out).reshape(
                B, Te, cfg.n_kv_heads, hd)
            xv = Lyr.dense(p["xattn"]["wv"], enc_out).reshape(
                B, Te, cfg.n_kv_heads, hd)
            if run.attn_impl == "naive":
                xa = Lyr.gqa_scores_softmax_out(q, xk, xv, None, hd ** -0.5)
            else:
                xa = blockwise_gqa(q, xk, xv, causal=False,
                                   q_block=run.q_block, kv_block=run.kv_block,
                                   constrain=_batch_cb(run))
            x = x + Lyr.dense(p["xattn"]["wo"],
                              xa.reshape(B, S, cfg.n_heads * hd))
            kv = dict(kv)
            kv["xk"], kv["xv"] = xk, xv  # static cross K/V for the cache
        if kind == "moe":
            h2, aux, _ = apply_moe(cfg, run, p["moe"],
                                   Lyr.rmsnorm(p["ln2"], x, cfg.norm_eps))
        else:
            h2 = Lyr.mlp(cfg, p["mlp"], Lyr.rmsnorm(p["ln2"], x, cfg.norm_eps))
        return x + h2, aux, kv
    if kind == "ssm":
        cb = _batch_cb(run) if run.pin_ssm else None
        h, state = Ssm.ssm_forward(cfg, p["ssm"],
                                   Lyr.rmsnorm(p["ln"], x, cfg.norm_eps),
                                   chunk=run.ssm_chunk or None,
                                   constrain=cb)
        return x + h, aux, {"h": state[0], "conv": state[1]}
    raise ValueError(kind)


def block_decode(cfg, run, p, x, cache_sl, cache_len, *, kind,
                 mrope_positions=None):
    """One layer, one token. cache_sl = this layer's cache slice (no L dim),
    read only. Returns (x, new, routes): ``new`` the step's cache rows
    (B,1,...) of an attention layer, to be written at cache_len, or an
    SSM layer's whole new state; ``routes`` as ``apply_moe`` gives them
    (None but for an expert layer)."""
    routes = None
    if kind in ("dense", "moe", "moe_dense0", "dec"):
        xin = Lyr.rmsnorm(p["ln1"], x, cfg.norm_eps)
        if cfg.use_mla:
            h, ckv, krope = mla_decode(cfg, run, p["attn"], xin,
                                       cache_sl["ckv"], cache_sl["krope"],
                                       cache_len)
            new = {"ckv": ckv, "krope": krope}
        else:
            h, k, v = gqa_decode(cfg, run, p["attn"], xin, cache_sl["k"],
                                 cache_sl["v"], cache_len,
                                 mrope_positions=mrope_positions)
            new = {"k": k, "v": v}
        x = x + h
        if kind == "dec":
            B = x.shape[0]
            hd = cfg.resolved_head_dim
            xq = Lyr.rmsnorm(p["lnx"], x, cfg.norm_eps)
            q = Lyr.dense(p["xattn"]["wq"], xq).reshape(B, 1, cfg.n_heads, hd)
            out = Lyr.gqa_scores_softmax_out(q, cache_sl["xk"], cache_sl["xv"],
                                             None, hd ** -0.5)
            x = x + Lyr.dense(p["xattn"]["wo"],
                              out.reshape(B, 1, cfg.n_heads * hd))
        xn = Lyr.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if kind == "moe":
            h2, _, routes = apply_moe(cfg, run, p["moe"], xn)
        else:
            h2 = Lyr.mlp(cfg, p["mlp"], xn)
        return x + h2, new, routes
    if kind == "ssm":
        h, (hs, conv) = Ssm.ssm_decode(cfg, p["ssm"],
                                       Lyr.rmsnorm(p["ln"], x, cfg.norm_eps),
                                       cache_sl["h"], cache_sl["conv"])
        return x + h, {"h": hs, "conv": conv}, routes
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(cfg, params, tokens, dtype=None):
    x = jnp.take(params["embed"]["w"], tokens, axis=0)
    return x if dtype is None else x.astype(dtype)


def _constrain(x, run, *spec):
    """Sharding constraint honoring divisibility (no-op without a mesh)."""
    if run.mesh is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec

    def size(ax):
        if ax is None:
            return 1
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = 1
        for a in axes:
            n *= run.mesh.shape[a]
        return n

    spec = tuple(ax if dim % size(ax) == 0 else None
                 for dim, ax in zip(x.shape, spec))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(run.mesh, PartitionSpec(*spec)))


def lm_logits(cfg, run, params, x):
    x = Lyr.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if cfg.tie_embeddings:
        logits = Lyr.weight_einsum("...d,vd->...v", x, params["embed"]["w"])
    else:
        logits = Lyr.dense(params["lm_head"], x)
    if cfg.padded_vocab != cfg.vocab_size:   # mask Megatron vocab padding
        pad_mask = jnp.arange(cfg.padded_vocab) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, -1e30)
    # keep the vocab dim model-sharded: without this GSPMD tends to gather
    # the full (B,S,V) logits per device (tens of GB at 1M tokens).
    return _constrain(logits, run, run.batch_axes, None, "model")


# ---------------------------------------------------------------------------
# Forward (train) / prefill
# ---------------------------------------------------------------------------


def _positions(batch, tokens):
    if "positions" in batch:
        return batch["positions"]
    B, S = tokens.shape
    return jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))


def _encode(cfg, run, params, frames):
    """Whisper encoder over (stub-)precomputed frame embeddings."""
    x = frames + Lyr.sinusoidal_positions(frames.shape[1],
                                          cfg.d_model)[None].astype(frames.dtype)
    pos = jnp.broadcast_to(jnp.arange(frames.shape[1], dtype=jnp.int32)[None],
                           frames.shape[:2])
    L = cfg.n_encoder_layers
    for i in range(L):
        p = jax.tree_util.tree_map(lambda a: a[i], params["enc_blocks"])
        x = _constrain(x, run, run.batch_axes, None, None)
        x, _, _ = block_fullseq(cfg, run, p, x, pos, kind="enc")
    return Lyr.rmsnorm(params["enc_norm"], x, cfg.norm_eps)


@jax.custom_vjp
def _grad_safe_barrier(x):
    # optimization_barrier has no differentiation rule on older jax; give
    # it an identity VJP (the barrier is a scheduling fence, gradient-wise
    # it IS the identity) so training paths can differentiate through it.
    return jax.lax.optimization_barrier(x)


def _grad_safe_barrier_fwd(x):
    return _grad_safe_barrier(x), None


def _grad_safe_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_grad_safe_barrier.defvjp(_grad_safe_barrier_fwd, _grad_safe_barrier_bwd)


def _scan_stack(cfg, run, blocks, x, positions, *, kind, build_cache,
                mrope_positions=None, mask_offset=0):
    """lax.scan over a uniform stacked block pytree."""

    def body(x, lp):
        # the barrier stops XLA folding downstream f32 upcasts into the
        # remat-saved residual stack (observed: layer inputs stored in BOTH
        # bf16 and f32, ~2x activation memory on deep stacks)
        x = _grad_safe_barrier(x)
        seq_ax = "model" if run.seq_parallel else None
        x = _constrain(x, run, run.batch_axes, seq_ax, None)
        x, aux, kv = block_fullseq(cfg, run, lp, x, positions, kind=kind,
                                   mrope_positions=mrope_positions,
                                   mask_offset=mask_offset)
        return x, (aux, kv if build_cache else 0)

    if run.remat:
        body = jax.checkpoint(body)
    if run.scan_layers:
        x, (auxs, kvs) = jax.lax.scan(body, x, blocks)
        return x, jnp.sum(auxs), (kvs if build_cache else None)
    n = jax.tree_util.tree_leaves(blocks)[0].shape[0]
    auxs, kvs = [], []
    for i in range(n):
        lp = jax.tree_util.tree_map(lambda a: a[i], blocks)
        x, (aux, kv) = body(x, lp)
        auxs.append(aux)
        kvs.append(kv)
    aux = jnp.sum(jnp.stack(auxs))
    if build_cache:
        kvs = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *kvs)
        return x, aux, kvs
    return x, aux, None


def _hybrid_fullseq(cfg, run, params, x, positions, build_cache):
    """Zamba2: mamba stack + shared attention block every k layers."""
    k_every = cfg.hybrid_attn_every
    ssm_caches, attn_caches = [], []

    def shared_fn(sp, x):
        return block_fullseq(cfg, run, sp, x, positions, kind="dense")

    def ssm_fn(lp, x):
        return block_fullseq(cfg, run, lp, x, positions, kind="ssm")

    if run.remat:
        shared_fn = jax.checkpoint(shared_fn)
        ssm_fn = jax.checkpoint(ssm_fn)

    for i in range(cfg.n_layers):
        x = _constrain(x, run, run.batch_axes, None, None)
        if k_every and i % k_every == 0:
            x, _, kv = shared_fn(params["shared"], x)
            if build_cache:
                attn_caches.append(kv)
        lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
        x, _, st = ssm_fn(lp, x)
        if build_cache:
            ssm_caches.append(st)
    cache = None
    if build_cache:
        cache = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ssm_caches)
        akv = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *attn_caches)
        cache = {"h": cache["h"], "conv": cache["conv"],
                 "ak": akv["k"], "av": akv["v"]}
    return x, cache


def forward(cfg, params, batch, run=RunCfg()):
    """Full-sequence forward. Returns (logits (B,S,V), aux dict)."""
    tokens = batch["tokens"]
    positions = _positions(batch, tokens)
    x = embed_tokens(cfg, params, tokens, run.stream_dtype)
    aux = jnp.zeros((), jnp.float32)
    mrope = batch.get("mrope_positions")

    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, run, params, batch["frames"])
        x = x + Lyr.sinusoidal_positions(x.shape[1],
                                         cfg.d_model)[None].astype(x.dtype)
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x = _constrain(x, run, run.batch_axes, None, None)
            x, a, _ = block_fullseq(cfg, run, lp, x, positions, kind="dec",
                                    enc_out=enc_out)
            aux = aux + a
        x = _constrain(x, run, run.batch_axes, None, None)
    elif cfg.family == "hybrid":
        x, _ = _hybrid_fullseq(cfg, run, params, x, positions, False)
    else:
        if cfg.family == "moe" and cfg.first_dense_layers:
            x, a, _ = _scan_stack(cfg, run, params["dense0"], x, positions,
                                  kind="moe_dense0", build_cache=False,
                                  mrope_positions=mrope)
            aux = aux + a
        x, a, _ = _scan_stack(cfg, run, params["blocks"], x, positions,
                              kind=main_block_kind(cfg), build_cache=False,
                              mrope_positions=mrope)
        aux = aux + a
    return lm_logits(cfg, run, params, x), {"moe_aux": aux}


# ---------------------------------------------------------------------------
# Prefill: forward + cache construction (cache length padded to max_len)
# ---------------------------------------------------------------------------


def _pad_cache_len(kvs, S, max_len, axis):
    if max_len <= S:
        return kvs
    pad = [(0, 0)] * 10

    def p(a, ax):
        cfgp = [(0, 0)] * a.ndim
        cfgp[ax] = (0, max_len - S)
        return jnp.pad(a, cfgp)
    return jax.tree_util.tree_map(lambda a: p(a, axis), kvs)


def prefill(cfg, params, batch, run=RunCfg(), max_len=None):
    """Returns (logits, cache). Cache seq dims padded to ``max_len``."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    max_len = max_len or S
    positions = _positions(batch, tokens)
    x = embed_tokens(cfg, params, tokens, run.stream_dtype)
    mrope = batch.get("mrope_positions")

    if cfg.is_encoder_decoder:
        enc_out = _encode(cfg, run, params, batch["frames"])
        x = x + Lyr.sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)
        kvs = []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            x = _constrain(x, run, run.batch_axes, None, None)
            x, _, kv = block_fullseq(cfg, run, lp, x, positions, kind="dec",
                                     enc_out=enc_out)
            kvs.append(kv)
        kvs = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *kvs)
        cache = {"k": kvs["k"], "v": kvs["v"], "xk": kvs["xk"],
                 "xv": kvs["xv"]}
        cache = {k: (_pad_cache_len(v, S, max_len, 2)
                     if k in ("k", "v") else v) for k, v in cache.items()}
    elif cfg.family == "hybrid":
        x, cache = _hybrid_fullseq(cfg, run, params, x, positions, True)
        for key in ("ak", "av"):
            cache[key] = _pad_cache_len(cache[key], S, max_len, 2)
    elif cfg.family == "ssm":
        x, _, cache = _scan_stack(cfg, run, params["blocks"], x, positions,
                                  kind="ssm", build_cache=True)
    else:
        caches = []
        if cfg.family == "moe" and cfg.first_dense_layers:
            x, _, kv0 = _scan_stack(cfg, run, params["dense0"], x, positions,
                                    kind="moe_dense0", build_cache=True,
                                    mrope_positions=mrope)
            caches.append(kv0)
        x, _, kv = _scan_stack(cfg, run, params["blocks"], x, positions,
                               kind=main_block_kind(cfg), build_cache=True,
                               mrope_positions=mrope)
        caches.append(kv)
        cache = jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a, axis=0), *caches) \
            if len(caches) > 1 else caches[0]
        cache = _pad_cache_len(cache, S, max_len, 2)
    return lm_logits(cfg, run, params, x[:, -1:]), cache


# ---------------------------------------------------------------------------
# Decode cache allocation (for dry-run / serving without a prefill pass)
# ---------------------------------------------------------------------------


def cache_struct(cfg, batch, max_len, dtype=None):
    """ShapeDtypeStructs (or zeros via init_cache) for the decode cache."""
    dt = jnp.dtype(dtype or cfg.dtype)
    hd = cfg.resolved_head_dim
    L, B, M = cfg.n_layers, batch, max_len

    def sd(shape, d=dt):
        return jax.ShapeDtypeStruct(shape, d)

    if cfg.family in ("dense", "vlm"):
        return {"k": sd((L, B, M, cfg.n_kv_heads, hd)),
                "v": sd((L, B, M, cfg.n_kv_heads, hd))}
    if cfg.family == "moe":
        if cfg.use_mla:
            return {"ckv": sd((L, B, M, cfg.kv_lora_rank)),
                    "krope": sd((L, B, M, cfg.qk_rope_head_dim))}
        return {"k": sd((L, B, M, cfg.n_kv_heads, hd)),
                "v": sd((L, B, M, cfg.n_kv_heads, hd))}
    H, P, N = cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    C = cfg.d_inner_ssm + 2 * cfg.ssm_n_groups * N
    if cfg.family == "ssm":
        return {"h": sd((L, B, H, P, N), jnp.float32),
                "conv": sd((L, B, cfg.ssm_conv - 1, C))}
    if cfg.family == "hybrid":
        I = n_shared_attn(cfg)
        return {"h": sd((L, B, H, P, N), jnp.float32),
                "conv": sd((L, B, cfg.ssm_conv - 1, C)),
                "ak": sd((I, B, M, cfg.n_kv_heads, hd)),
                "av": sd((I, B, M, cfg.n_kv_heads, hd))}
    if cfg.is_encoder_decoder:
        return {"k": sd((L, B, M, cfg.n_kv_heads, hd)),
                "v": sd((L, B, M, cfg.n_kv_heads, hd)),
                "xk": sd((L, B, cfg.encoder_seq, cfg.n_kv_heads, hd)),
                "xv": sd((L, B, cfg.encoder_seq, cfg.n_kv_heads, hd))}
    raise ValueError(cfg.family)


def init_cache(cfg, batch, max_len, dtype=None):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  cache_struct(cfg, batch, max_len, dtype))


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def decode_step(cfg, params, token, cache, cache_len, run=RunCfg(),
                mrope_positions=None):
    """token (B,1) int32; cache per ``cache_struct``; cache_len () int32.

    Returns (logits (B,1,V), new_cache, routes): ``routes`` (L_moe, B, K),
    the experts each token routed to in each scanned expert layer, where
    the expert layer reports them (``apply_moe``), else None. Attention
    layers read the cache as given and return their rows; one write per
    leaf then places all layers' rows at cache_len (``write_rows``).
    """
    routes = None
    x = embed_tokens(cfg, params, token, run.stream_dtype)
    kind = main_block_kind(cfg)

    if cfg.is_encoder_decoder:
        x = x + jax.lax.dynamic_slice_in_dim(
            Lyr.sinusoidal_positions(cache.get("k").shape[2], cfg.d_model),
            cache_len, 1, axis=0)[None].astype(x.dtype)
        rows = []
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            csl = jax.tree_util.tree_map(lambda a: a[i], cache)
            x, r, _ = block_decode(cfg, run, lp, x, csl, cache_len,
                                   kind="dec")
            rows.append(r)
        new_cache = write_rows(
            cache, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *rows),
            cache_len)
    elif cfg.family == "hybrid":
        k_every = cfg.hybrid_attn_every
        hs, convs, aks, avs = [], [], [], []
        inv = 0
        for i in range(cfg.n_layers):
            if k_every and i % k_every == 0:
                sp = params["shared"]
                xin = Lyr.rmsnorm(sp["ln1"], x, cfg.norm_eps)
                h, k, v = gqa_decode(cfg, run, sp["attn"], xin,
                                     cache["ak"][inv], cache["av"][inv],
                                     cache_len)
                x = x + h
                x = x + Lyr.mlp(cfg, sp["mlp"],
                                Lyr.rmsnorm(sp["ln2"], x, cfg.norm_eps))
                aks.append(k)
                avs.append(v)
                inv += 1
            lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            csl = {"h": cache["h"][i], "conv": cache["conv"][i]}
            x, nc, _ = block_decode(cfg, run, lp, x, csl, cache_len,
                                    kind="ssm")
            hs.append(nc["h"])
            convs.append(nc["conv"])
        new_cache = write_rows(
            {**cache, "h": jnp.stack(hs), "conv": jnp.stack(convs)},
            {"ak": jnp.stack(aks), "av": jnp.stack(avs)}, cache_len)
    else:
        # uniform stack: scan the layers over their index into the cache,
        # so each reads its slice in place (a scan over the cache itself,
        # or over a slice of it, copies it). MoE stacks with a leading
        # dense layer run dense0 as a python loop, then scan the remainder.
        n_dense0 = cfg.first_dense_layers if cfg.family == "moe" else 0

        def layer_of(i):
            return jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                cache)

        parts = []
        for i in range(n_dense0):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["dense0"])
            x, r, _ = block_decode(cfg, run, lp, x, layer_of(i), cache_len,
                                   kind="moe_dense0",
                                   mrope_positions=mrope_positions)
            parts.append(jax.tree_util.tree_map(lambda a: a[None], r))

        def scan_body(x, inp):
            lp, i = inp
            x, r, rt = block_decode(cfg, run, lp, x, layer_of(i), cache_len,
                                    kind=kind,
                                    mrope_positions=mrope_positions)
            return x, (r, rt)

        n = jax.tree_util.tree_leaves(params["blocks"])[0].shape[0]
        if run.scan_layers:
            x, (rows, routes) = jax.lax.scan(
                scan_body, x,
                (params["blocks"], n_dense0 + jnp.arange(n, dtype=jnp.int32)))
        else:
            outs = []
            for i in range(n):
                lp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
                x, out = scan_body(x, (lp, n_dense0 + i))
                outs.append(out)
            rows, routes = jax.tree_util.tree_map(
                lambda *a: jnp.stack(a), *outs)
        parts.append(rows)
        new = jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a, axis=0), *parts) \
            if len(parts) > 1 else parts[0]
        # an SSM layer returns its whole new state, not a row
        new_cache = new if cfg.family == "ssm" else \
            write_rows(cache, new, cache_len)

    return lm_logits(cfg, run, params, x), new_cache, routes


def serve_step(cfg, params, token, cache, cache_len, rng, run=RunCfg(),
               temperature=0.0):
    """decode_step + sampling -> (next_token (B,1), new_cache)."""
    logits, new_cache, _ = decode_step(cfg, params, token, cache, cache_len,
                                       run)
    lg = logits[:, -1, :].astype(jnp.float32)
    if temperature and temperature > 0:
        nxt = jax.random.categorical(rng, lg / temperature, axis=-1)
    else:
        nxt = jnp.argmax(lg, axis=-1)
    return nxt[:, None].astype(jnp.int32), new_cache


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def lm_loss(cfg, params, batch, run=RunCfg()):
    """Causal LM cross-entropy (labels == -1 ignored) + MoE aux.

    Written shard-wise over the vocab dim: the lse reduction and the
    one-hot pick both reduce over V, so with logits constrained to
    (batch, None, "model") GSPMD lowers them to local reductions + psum
    instead of gathering the (B,S,V) tensor.
    """
    logits, aux = forward(cfg, params, batch, run)
    labels = batch["labels"]
    lg = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lg, axis=-1, keepdims=True))
    lse = jnp.log(jnp.sum(jnp.exp(lg - m), axis=-1)) + m[..., 0]
    onehot = jax.nn.one_hot(jnp.maximum(labels, 0), cfg.padded_vocab,
                            dtype=lg.dtype)
    onehot = _constrain(onehot, run, run.batch_axes, None, "model")
    picked = jnp.sum(lg * onehot, axis=-1)
    nll = lse - picked
    valid = (labels >= 0).astype(jnp.float32)
    loss = jnp.sum(nll * valid) / jnp.maximum(jnp.sum(valid), 1.0)
    return loss + run.aux_coef * aux["moe_aux"], {
        "loss": loss, "moe_aux": aux["moe_aux"]}
