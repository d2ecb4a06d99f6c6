"""Operations and bytes of the work a configuration defines, from shapes.

These count what the configuration states (its served dtype, its widths),
not what the program happens to move: a program that holds wider weights
than stated reads a lower roofline share, and one that meets the stated
work reads 100% at best.
"""
from __future__ import annotations

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1,
               "float8": 1}


def model_shapes(cfg: dict) -> dict:
    """Widths from a configuration file's model keys. The benchmark's
    counts and its reference know one layer: pre-norm attention without
    bias (GQA, optional qk-norm) and a SiLU-gated MLP."""
    if cfg["hidden_act"] != "silu" or cfg.get("attention_bias"):
        raise ValueError(f"{cfg.get('name')}: the benchmark knows the "
                         "SiLU-gated layer without attention bias only")
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "hkv": cfg["num_key_value_heads"], "hd": hd,
            "ff": cfg["intermediate_size"], "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"],
            "tied": bool(cfg["tie_word_embeddings"])}


def layer_matmul_params(s: dict) -> int:
    attn = s["d"] * s["hd"] * (2 * s["h"] + 2 * s["hkv"])
    mlp = 3 * s["d"] * s["ff"]
    return attn + mlp


def param_count(cfg: dict) -> int:
    """Parameters the served model holds (embedding, head, layers; norms
    included)."""
    s = model_shapes(cfg)
    per_layer = layer_matmul_params(s) + 2 * s["d"]
    if cfg.get("qk_norm", False):
        per_layer += 2 * s["hd"]
    head = 0 if s["tied"] else s["vocab"] * s["d"]
    return s["vocab"] * s["d"] + head + s["layers"] * per_layer + s["d"]


def weight_bytes(cfg: dict) -> int:
    """Bytes one decode step must read of the weights, at the stated dtype:
    every layer and the head; of the embedding only the rows looked up,
    which is nothing next to the rest (a tied head reads it whole)."""
    s = model_shapes(cfg)
    b = DTYPE_BYTES[cfg["torch_dtype"]]
    n = s["layers"] * (layer_matmul_params(s) + 2 * s["d"]) + s["d"]
    n += s["vocab"] * s["d"]                 # head (tied or not)
    return n * b


def kv_bytes_per_position(cfg: dict) -> int:
    s = model_shapes(cfg)
    return 2 * s["layers"] * s["hkv"] * s["hd"] * \
        DTYPE_BYTES[cfg["torch_dtype"]]


def flops_per_token(cfg: dict, position: int) -> int:
    """Forward FLOPs for one token at ``position`` (0-based): every matmul
    of the layers and the head, and attention over ``position + 1`` keys."""
    s = model_shapes(cfg)
    matmul = 2 * (s["layers"] * layer_matmul_params(s) + s["vocab"] * s["d"])
    attn = 4 * s["layers"] * s["h"] * s["hd"] * (position + 1)
    return matmul + attn


def decode_step_least_s(cfg: dict, peaks: dict, live: float,
                        kv_positions: float) -> tuple:
    """Least time of one decode step on ``peaks``: the larger of its bytes
    (stated-dtype weights plus the KV of ``kv_positions`` cached positions
    summed over live slots) over HBM bandwidth and its FLOPs (``live``
    tokens) over the peak of the stated dtype. Returns (seconds, bound)."""
    byts = weight_bytes(cfg) + kv_bytes_per_position(cfg) * kv_positions
    mean_pos = kv_positions / live if live else 0.0
    flops = live * flops_per_token(cfg, int(mean_pos))
    t_mem = byts / peaks["hbm_bytes_per_s"]
    t_flop = flops / peaks["flops_per_s"][cfg["torch_dtype"]]
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")


def scan_cost(rows: int, dim: int, q: float, k: int = 1) -> tuple:
    """(ops, bytes) of one int8 MIPS call over ``rows`` x ``dim`` int8 with
    f32 per-row scales, for ``q`` queries: the store and its scales read
    once, the int8 queries with their scales in, ``k`` (score, row) pairs
    out per query."""
    ops = 2 * q * rows * dim
    byts = rows * dim + rows * 4 + q * (dim + 4) + q * k * 8
    return ops, byts


def scan_least_s(rows: int, dim: int, q: float, peaks: dict) -> tuple:
    ops, byts = scan_cost(rows, dim, q)
    t_mem = byts / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["flops_per_s"]["int8"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
