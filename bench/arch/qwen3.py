"""Qwen3 (``model_type`` "qwen3"): pre-norm attention without bias (GQA,
RMSNorm on each query and key head when ``qk_norm``), a SiLU-gated MLP,
RMSNorm, rotate-half RoPE, and a head tied to the embedding or not.

The benchmark's yardstick for this one layer, repeated
``num_hidden_layers`` times: the program widths the configuration file
states, the plain float32 reference and its float8 control, and the
parameters, bytes and FLOPs of the stated work. The harness finds this
module by the configuration's ``model_type`` (``spec.load_arch``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from harness import reference as R
from harness.costs import DTYPE_BYTES


def program_widths(cfg: dict) -> dict:
    """The program's ``ModelConfig`` attributes with the values the
    configuration file states."""
    return {"d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "d_ff": cfg["intermediate_size"],
            "vocab_size": cfg["vocab_size"],
            "n_layers": cfg["num_hidden_layers"],
            "resolved_head_dim": cfg["head_dim"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "attn_bias": cfg["attention_bias"],
            "qk_norm": cfg["qk_norm"],
            "gated_mlp": cfg["hidden_act"] == "silu",
            "rope_theta": cfg["rope_theta"],
            "norm_eps": cfg["rms_norm_eps"],
            "dtype": cfg["torch_dtype"]}


def model_shapes(cfg: dict) -> dict:
    """Widths from a configuration file's model keys."""
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


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------


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


def decode_step_cost(cfg: dict, live: float, kv_positions: float) -> tuple:
    """(bytes, FLOPs) of one decode step: the stated-dtype weights plus the
    KV of ``kv_positions`` cached positions summed over the live slots,
    and ``live`` tokens at their mean position. Every weight is read
    whatever the live slots ask of it."""
    byts = weight_bytes(cfg) + kv_bytes_per_position(cfg) * kv_positions
    mean_pos = kv_positions / live if live else 0.0
    return byts, live * flops_per_token(cfg, int(mean_pos))


# ---------------------------------------------------------------------------
# reference: weights from the seed
# ---------------------------------------------------------------------------


def _layer(key, s):
    d, hd, h, hkv, ff = s["d"], s["hd"], s["h"], s["hkv"], s["ff"]
    k_attn, k_mlp = jax.random.split(key, 6)[:2]
    ka = jax.random.split(k_attn, 4)
    km = jax.random.split(k_mlp, 3)
    p = {"wq": R._normal(ka[0], (d, h * hd), d ** -0.5),
         "wk": R._normal(ka[1], (d, hkv * hd), d ** -0.5),
         "wv": R._normal(ka[2], (d, hkv * hd), d ** -0.5),
         "wo": R._normal(ka[3], (h * hd, d), (h * hd) ** -0.5),
         "w1": R._normal(km[0], (d, ff), d ** -0.5),
         "w2": R._normal(km[1], (ff, d), ff ** -0.5),
         "w3": R._normal(km[2], (d, ff), d ** -0.5)}
    return p


def init_weights(cfg: dict, seed) -> dict:
    """The model's float32 weights from ``seed`` (below 2**31), made on the
    device in one call by the program's initialisation (normal, fan-in
    scaled, the same key schedule). Norm scales are ones at
    initialisation."""
    return _init(R._frozen(cfg), jnp.asarray(seed, jnp.int32))


@partial(jax.jit, static_argnums=(0,))
def _init(cfg_items, seed):
    s = model_shapes(dict(cfg_items))
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    vp = R.padded_vocab(s["vocab"])
    w = {"embed": R._normal(ks[0], (vp, s["d"]), s["d"] ** -0.5)}
    if not s["tied"]:
        w["head"] = R._normal(ks[1], (s["d"], vp), s["d"] ** -0.5)
    w["layers"] = jax.vmap(lambda k: _layer(k, s))(
        jax.random.split(ks[2], s["layers"]))
    return w


# ---------------------------------------------------------------------------
# reference: forward
# ---------------------------------------------------------------------------


def forward(cfg: dict, w: dict, tokens, fp8: bool = False):
    """Logits (B, S, vocab) of a causal pass over ``tokens`` (B, S); with
    ``fp8`` every linear layer's operands in float8 (the control)."""
    s = model_shapes(cfg)
    eps = cfg["rms_norm_eps"]
    B, S = tokens.shape
    H, Hkv, hd = s["h"], s["hkv"], s["hd"]
    G = H // Hkv
    pos = jnp.arange(S)
    emb = R._q8(w["embed"], -1) if fp8 else w["embed"]
    x = emb[tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, p):
        h = R._rms(x, eps)
        q = R._linear(h, p["wq"], fp8)
        k = R._linear(h, p["wk"], fp8)
        v = R._linear(h, p["wv"], fp8)
        q = q.reshape(B, S, H, hd)
        k = k.reshape(B, S, Hkv, hd)
        v = v.reshape(B, S, Hkv, hd)
        if cfg.get("qk_norm"):
            q, k = R._rms(q, eps), R._rms(k, eps)
        q = R._rope(q, pos, cfg["rope_theta"])
        k = R._rope(k, pos, cfg["rope_theta"])
        qg = q.reshape(B, S, Hkv, G, hd)
        a = jnp.einsum("bskgd,btkd->bkgst", qg, k) * hd ** -0.5
        a = jax.nn.softmax(jnp.where(causal, a, -1e30), axis=-1)
        o = jnp.einsum("bkgst,btkd->bskgd", a, v).reshape(B, S, H * hd)
        x = x + R._linear(o, p["wo"], fp8)
        h = R._rms(x, eps)
        u = jax.nn.silu(R._linear(h, p["w1"], fp8)) * \
            R._linear(h, p["w3"], fp8)
        return x + R._linear(u, p["w2"], fp8), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = R._rms(x, eps)
    head = w["embed"].T if s["tied"] else w["head"]
    return R._linear(x, head, fp8)[..., :s["vocab"]]
