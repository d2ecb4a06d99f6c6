"""Logical-axis sharding rules -> NamedSharding pytrees.

One rule engine for params / optimizer state / caches / batches. Rules are
(path-regex -> per-dim logical axes); logical axes resolve to mesh axes only
when the dim size divides the shard count (else that dim replicates) — this
is what lets e.g. grok's 8 experts fall back from expert-parallel to
TP-on-d_ff, or mamba2's odd in_proj width replicate, without per-arch
special cases.

Logical axes:
  TP    -> "model"
  FSDP  -> ("pod", "data") (as available / divisible)
  BATCH -> ("pod", "data")
  SEQ   -> "model"   (KV-sequence parallel for decode caches)
  EP    -> "model"   (expert parallel)
  REP   -> replicated
"""
from __future__ import annotations

import re
from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

TP, FSDP, BATCH, SEQ, EP, REP = "TP", "FSDP", "BATCH", "SEQ", "EP", "REP"


def _resolve(logical: str, dim: int, mesh) -> Optional[object]:
    """Map a logical axis to mesh axes, honoring divisibility."""
    names = mesh.axis_names
    if logical == REP:
        return None
    if logical in (TP, SEQ, EP):
        if "model" in names and dim % mesh.shape["model"] == 0:
            return "model"
        return None
    if logical in (FSDP, BATCH):
        axes = [a for a in ("pod", "data") if a in names]
        # prefer the full product, then drop axes from the left
        while axes:
            n = 1
            for a in axes:
                n *= mesh.shape[a]
            if dim % n == 0:
                return tuple(axes) if len(axes) > 1 else axes[0]
            axes = axes[1:]
        return None
    raise ValueError(logical)


def spec_for(shape, logical_axes, mesh) -> P:
    assert len(shape) >= len(logical_axes), (shape, logical_axes)
    # right-align the rule (leading stack dims replicate)
    pad = len(shape) - len(logical_axes)
    axes = [REP] * pad + list(logical_axes)
    used = set()
    out = []
    for dim, ax in zip(shape, axes):
        r = _resolve(ax, dim, mesh)
        # a mesh axis may appear only once in a PartitionSpec
        flat = r if isinstance(r, tuple) else (r,) if r else ()
        if any(f in used for f in flat):
            r = None
        for f in flat:
            used.add(f)
        out.append(r)
    return P(*out)


# ---------------------------------------------------------------------------
# Param rules (matched against "/"-joined pytree path, first match wins)
#
# Attention projections are HEAD-AWARE: the flat (d, H*hd) out-dim may only
# TP-shard when the head count divides the model axis — otherwise the
# (B,S,H,hd) reshape cuts across shard boundaries and GSPMD re-gathers the
# activations every layer (measured: ~4.3 GB/layer of f32 all-gathers on
# llama3.2-3b whose 24 heads don't divide 16). Non-divisible q-heads =>
# replicate the projection (redundant compute over "model", zero resharding
# — what Megatron does when TP > heads); divisible q-heads with
# non-divisible kv-heads => Megatron-GQA kv replication (+ repeat_kv in the
# attention kernel).
# ---------------------------------------------------------------------------

PARAM_RULES = [
    (r"embed/w$", [TP, FSDP]),
    (r"lm_head/w$", [FSDP, TP]),
    (r"lm_head/b$", [TP]),
    # attention projections (head-awareness patched in param_specs)
    (r"(attn|xattn)/wq/w$", [FSDP, TP]),
    (r"(attn|xattn)/w[kv]/w$", [FSDP, TP]),
    (r"(attn|xattn)/wq/b$", [TP]),
    (r"(attn|xattn)/w[kv]/b$", [TP]),
    (r"(attn|xattn)/wo/w$", [TP, FSDP]),
    (r"(attn|xattn)/wo/b$", [REP]),
    # MLA
    (r"attn/wdkv/w$", [FSDP, REP]),
    (r"attn/wuk$", [REP, TP, REP]),
    (r"attn/wuv$", [REP, TP, REP]),
    # MoE experts: EP on the expert dim; FSDP the d_model dim. When E does
    # not divide the model axis (grok: 8 experts on 16-way model), EP
    # resolves to None and the d_ff dim TP-shards instead via the next rule
    # component (handled by divisibility in spec_for).
    (r"moe/experts/w[13]$", [EP, FSDP, TP]),
    (r"moe/experts/w2$", [EP, TP, FSDP]),
    (r"moe/router/w$", [REP, REP]),
    (r"moe/shared/w[13]/w$", [FSDP, TP]),
    (r"moe/shared/w2/w$", [TP, FSDP]),
    # dense MLPs
    (r"mlp/w[13]/w$", [FSDP, TP]),
    (r"mlp/w2/w$", [TP, FSDP]),
    # SSM
    (r"ssm/in_proj/w$", [FSDP, TP]),
    (r"ssm/out_proj/w$", [TP, FSDP]),
    (r"ssm/conv_w$", [REP, TP]),
    (r"ssm/conv_b$", [TP]),
    (r"ssm/(A_log|dt_bias|D)$", [REP]),
    # norms and anything else small
    (r".*", [REP]),
]

# EP constraint: when experts ARE expert-parallel (E % model == 0) the
# d_ff dim must stay unsharded for the all-to-all path; spec_for's
# used-axis bookkeeping enforces that automatically ("model" appears once).


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def heads_shardable(cfg, mesh):
    """(q_heads_ok, kv_heads_ok) on this mesh's model axis."""
    if "model" not in mesh.axis_names:
        return False, False
    n = mesh.shape["model"]
    q_ok = cfg.n_heads > 0 and cfg.n_heads % n == 0
    kv_ok = cfg.n_kv_heads > 0 and cfg.n_kv_heads % n == 0
    if cfg.use_mla:  # MLA: per-head expansion weights (r,H,*) shard on H
        kv_ok = q_ok
    return q_ok, kv_ok


def param_specs(params_or_struct, mesh, cfg=None):
    """PartitionSpec pytree for a param pytree (works on ShapeDtypeStructs).

    ``cfg`` enables head-aware attention sharding (see PARAM_RULES note).
    """
    q_ok, kv_ok = heads_shardable(cfg, mesh) if cfg is not None else (True,
                                                                      True)

    def one(path, leaf):
        p = _path_str(path)
        for pat, rule in PARAM_RULES:
            if re.search(pat, p):
                rule = list(rule)
                if re.search(r"(attn|xattn)/wq/", p) and not q_ok:
                    rule = [FSDP, REP] if p.endswith("/w") else [REP]
                elif re.search(r"(attn|xattn)/w[kv]/", p) and not kv_ok:
                    rule = [FSDP, REP] if p.endswith("/w") else [REP]
                elif re.search(r"(attn|xattn)/wo/w$", p) and not q_ok:
                    rule = [REP, FSDP]
                elif re.search(r"attn/wu[kv]$", p) and not q_ok:
                    rule = [REP, REP, REP]
                return spec_for(leaf.shape, rule, mesh)
        raise AssertionError(p)

    return jax.tree_util.tree_map_with_path(one, params_or_struct)


def param_shardings(params_or_struct, mesh, cfg=None):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  param_specs(params_or_struct, mesh, cfg))


# ---------------------------------------------------------------------------
# Cache rules (decode KV / SSM state). Leading dim is the layer stack.
# ---------------------------------------------------------------------------

CACHE_RULES = [
    (r"(^|/)(k|v|ak|av)$", [REP, BATCH, SEQ, REP, REP]),   # (L,B,M,Hkv,hd)
    (r"(^|/)(ckv|krope)$", [REP, BATCH, SEQ, REP]),        # (L,B,M,r)
    (r"(^|/)x[kv]$", [REP, BATCH, REP, REP, REP]),         # (L,B,Tenc,H,hd)
    (r"(^|/)h$", [REP, BATCH, TP, REP, REP]),              # (L,B,H,P,N)
    (r"(^|/)conv$", [REP, BATCH, REP, TP]),                # (L,B,W-1,C)
    (r".*", [REP]),
]


def cache_specs(cache_struct, mesh):
    def one(path, leaf):
        p = _path_str(path)
        for pat, rule in CACHE_RULES:
            if re.search(pat, p):
                return spec_for(leaf.shape, rule, mesh)
        raise AssertionError(p)

    return jax.tree_util.tree_map_with_path(one, cache_struct)


def cache_shardings(cache_struct, mesh):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  cache_specs(cache_struct, mesh))


# ---------------------------------------------------------------------------
# Batch rules
# ---------------------------------------------------------------------------

BATCH_RULES = [
    (r"mrope_positions$", [REP, BATCH, REP]),              # (3,B,S)
    (r"frames$", [BATCH, REP, REP]),                       # (B,Tenc,d)
    (r".*", [BATCH, REP]),                                 # tokens/labels/pos
]


def batch_specs(batch_struct, mesh):
    def one(path, leaf):
        p = _path_str(path)
        for pat, rule in BATCH_RULES:
            if re.search(pat, p):
                return spec_for(leaf.shape, rule, mesh)
        raise AssertionError(p)

    return jax.tree_util.tree_map_with_path(one, batch_struct)


def batch_shardings(batch_struct, mesh):
    return jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s),
                                  batch_specs(batch_struct, mesh))


def replicated(mesh):
    return NamedSharding(mesh, P())
