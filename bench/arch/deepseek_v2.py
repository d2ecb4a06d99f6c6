"""DeepSeek-V2 (``model_type`` "deepseek_v2"), as DeepSeek-V2-Lite
publishes it: pre-norm multi-head latent attention (MLA) without q
compression, with yarn rope on a decoupled rope head; a leading dense
SiLU-gated MLP layer, then expert layers of routed experts (softmax gate,
greedy top-k, weights renormalised only under ``norm_topk_prob``, times
``routed_scaling_factor``) beside shared experts; RMSNorm; an untied head.

The benchmark's yardstick for this architecture: the program widths the
configuration file states, the plain float32 reference and its float8
control, and the parameters, bytes and FLOPs of the stated work. The
harness finds this module by the configuration's ``model_type``
(``spec.load_arch``).

The chip's share of an expert-parallel deployment: the file's
``n_routed_experts`` is the number of routed experts this chip holds, from
``expert_offset``, of ``published_n_routed_experts``; the router keeps its
published width and the reference, like the program, adds only the held
experts' part of the routed result (the shared experts are whole).

Departures from the published model, none of which changes what random
weights compute:

* Rope rotates halves (dims i and i + 32 of the 64-dim rope head) where
  the published code rotates interleaved pairs: a fixed permutation of
  the 64 rope columns of ``wq`` and ``wdkv``.
* Only the held experts are computed (the cut above).
* Each layer's float32 weights are made inside the layer scan from the
  layer's key, so the whole model (12.4 GB in float32 at the share) is
  never resident at once on one 16 GB chip.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from harness import reference as R
from harness.costs import DTYPE_BYTES


def model_shapes(cfg: dict) -> dict:
    """Widths and counts from a configuration file's model keys (its
    scalars: the reference's compiled programs are keyed by them alone)."""
    if (cfg["hidden_act"] != "silu" or cfg.get("attention_bias")
            or cfg.get("q_lora_rank") is not None
            or cfg["scoring_func"] != "softmax"
            or cfg["topk_method"] != "greedy" or cfg["moe_layer_freq"] != 1):
        raise ValueError(f"{cfg.get('name')}: the benchmark knows DeepSeek-V2"
                         " with a SiLU MLP, no attention bias, no q "
                         "compression, and a greedy softmax gate on every "
                         "layer after the dense ones only")
    total = cfg["published_n_routed_experts"]
    held = cfg["n_routed_experts"]
    if held * cfg["expert_parallel"] != total or \
            not 0 <= cfg["expert_offset"] <= total - held:
        raise ValueError(f"{cfg.get('name')}: {held} experts held from "
                         f"{cfg['expert_offset']} on {cfg['expert_parallel']}"
                         f" chips is not a share of {total}")
    n_dense = cfg["first_k_dense_replace"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "vd": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "dense_ff": cfg["intermediate_size"],
            "ffe": cfg["moe_intermediate_size"],
            "shared_ff": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            "experts": total, "held": held, "lo": cfg["expert_offset"],
            "k": cfg["num_experts_per_tok"], "layers": cfg["num_hidden_layers"],
            "dense": n_dense, "moe": cfg["num_hidden_layers"] - n_dense,
            "vocab": cfg["vocab_size"],
            "tied": bool(cfg["tie_word_embeddings"])}


def program_widths(cfg: dict) -> dict:
    """The program's ``ModelConfig`` attributes with the values the
    configuration file states."""
    rs = cfg["rope_scaling"]
    return {"family": "moe",
            "d_model": cfg["hidden_size"],
            "n_heads": cfg["num_attention_heads"],
            "n_kv_heads": cfg["num_key_value_heads"],
            "vocab_size": cfg["vocab_size"],
            "n_layers": cfg["num_hidden_layers"],
            "first_dense_layers": cfg["first_k_dense_replace"],
            "d_ff_dense": cfg["intermediate_size"],
            "d_ff_expert": cfg["moe_intermediate_size"],
            "n_experts": cfg["published_n_routed_experts"],
            "n_experts_held": cfg["n_routed_experts"],
            "expert_offset": cfg["expert_offset"],
            "experts_per_tok": cfg["num_experts_per_tok"],
            "n_shared_experts": cfg["n_shared_experts"],
            "norm_topk_prob": cfg["norm_topk_prob"],
            "routed_scaling_factor": cfg["routed_scaling_factor"],
            "use_mla": True,
            "kv_lora_rank": cfg["kv_lora_rank"],
            "q_lora_rank": cfg["q_lora_rank"] or 0,
            "qk_nope_head_dim": cfg["qk_nope_head_dim"],
            "qk_rope_head_dim": cfg["qk_rope_head_dim"],
            "v_head_dim": cfg["v_head_dim"],
            "tie_embeddings": cfg["tie_word_embeddings"],
            "attn_bias": cfg["attention_bias"],
            "gated_mlp": cfg["hidden_act"] == "silu",
            "rope_theta": cfg["rope_theta"],
            "rope_kind": rs["type"],
            "rope_factor": rs["factor"],
            "yarn_beta_fast": rs["beta_fast"],
            "yarn_beta_slow": rs["beta_slow"],
            "yarn_original_max_pos": rs["original_max_position_embeddings"],
            "yarn_mscale": rs["mscale"],
            "yarn_mscale_all_dim": rs["mscale_all_dim"],
            "norm_eps": cfg["rms_norm_eps"],
            "dtype": cfg["torch_dtype"]}


# ---------------------------------------------------------------------------
# operations and bytes
# ---------------------------------------------------------------------------


def mla_params(s: dict) -> int:
    """q, the latent down-projection with the rope head, the latent's up-
    projections to per-head keys and values, and the output."""
    d, h, r = s["d"], s["h"], s["r"]
    return (d * h * (s["nope"] + s["rope"]) + d * (r + s["rope"])
            + r * h * (s["nope"] + s["vd"]) + h * s["vd"] * d)


def expert_params(s: dict) -> int:
    return 3 * s["d"] * s["ffe"]


def outside_experts_per_layer(s: dict) -> tuple:
    """Parameters of (a dense layer, an expert layer outside its routed
    experts): attention with its latent norm, two norms, and the MLP, or
    the router and the shared experts."""
    d = s["d"]
    common = mla_params(s) + s["r"] + 2 * d
    return (common + 3 * d * s["dense_ff"],
            common + d * s["experts"] + 3 * d * s["shared_ff"])


def param_count(cfg: dict) -> int:
    """Parameters this chip holds (embedding, head, layers with the held
    experts; norms included)."""
    s = model_shapes(cfg)
    dense, moe = outside_experts_per_layer(s)
    head = 0 if s["tied"] else s["vocab"] * s["d"]
    return (s["vocab"] * s["d"] + head + s["d"] + s["dense"] * dense
            + s["moe"] * (moe + s["held"] * expert_params(s)))


def weight_bytes_outside_experts(cfg: dict) -> int:
    """Bytes one decode step reads of the weights outside the routed
    experts, at the stated dtype: every layer and the head; of the
    embedding only the rows looked up."""
    s = model_shapes(cfg)
    dense, moe = outside_experts_per_layer(s)
    n = s["dense"] * dense + s["moe"] * moe + s["d"] + s["vocab"] * s["d"]
    return n * DTYPE_BYTES[cfg["torch_dtype"]]


def experts_touched(cfg: dict, live: float) -> float:
    """Held experts of one expert layer that ``live`` tokens route to, as
    expected under uniform routing: held x (1 - (1 - k/E)^live)."""
    s = model_shapes(cfg)
    return s["held"] * (1.0 - (1.0 - s["k"] / s["experts"]) ** live)


def kv_bytes_per_position(cfg: dict) -> int:
    """The latent cache: the normalised latent and the rope key, a layer."""
    s = model_shapes(cfg)
    return s["layers"] * (s["r"] + s["rope"]) * \
        DTYPE_BYTES[cfg["torch_dtype"]]


def flops_per_token(cfg: dict, position: int) -> int:
    """Forward FLOPs for one token at ``position`` (0-based): every matmul
    of the layers (the routed experts at the held share, k x held / E
    experts a token) and the head, and attention over ``position + 1``
    keys with 192-wide query-key heads and 128-wide values."""
    s = model_shapes(cfg)
    d = s["d"]
    routed = s["k"] * s["held"] * expert_params(s) // s["experts"]
    layer = mla_params(s)
    matmul = (s["layers"] * layer + s["dense"] * 3 * d * s["dense_ff"]
              + s["moe"] * (d * s["experts"] + 3 * d * s["shared_ff"]
                            + routed)
              + s["vocab"] * d)
    attn = 2 * s["layers"] * s["h"] * (s["nope"] + s["rope"] + s["vd"]) * \
        (position + 1)
    return 2 * matmul + attn


def decode_step_cost(cfg: dict, live: float, kv_positions: float) -> tuple:
    """(bytes, FLOPs) of one decode step: the stated-dtype weights outside
    the routed experts, the held experts that ``live`` tokens touch
    (``experts_touched``) in each expert layer, and the latent cache of
    ``kv_positions`` cached positions summed over the live slots; ``live``
    tokens at their mean position."""
    s = model_shapes(cfg)
    experts = s["moe"] * experts_touched(cfg, live) * expert_params(s) * \
        DTYPE_BYTES[cfg["torch_dtype"]]
    byts = (weight_bytes_outside_experts(cfg) + experts
            + kv_bytes_per_position(cfg) * kv_positions)
    mean_pos = kv_positions / live if live else 0.0
    return byts, live * flops_per_token(cfg, int(mean_pos))


# ---------------------------------------------------------------------------
# reference: weights from the seed
# ---------------------------------------------------------------------------


def _dense(key, d_in, d_out):
    return R._normal(key, (d_in, d_out), d_in ** -0.5)


def _mlp(key, d, ff):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w1": _dense(k1, d, ff), "w2": _dense(k2, ff, d),
            "w3": _dense(k3, d, ff)}


def _mla(key, s):
    d, h, r = s["d"], s["h"], s["r"]
    ks = jax.random.split(key, 6)
    return {"wq": _dense(ks[0], d, h * (s["nope"] + s["rope"])),
            "wdkv": _dense(ks[1], d, r + s["rope"]),
            "wuk": R._normal(ks[2], (r, h, s["nope"]), r ** -0.5),
            "wuv": R._normal(ks[3], (r, h, s["vd"]), r ** -0.5),
            "wo": _dense(ks[4], h * s["vd"], d)}


def _experts(key, s, a, b):
    """The held experts' (a, b) matrices: the layer draws all its routed
    experts' in one (E, a, b) draw, and this chip holds ``held`` of them
    from ``lo``."""
    w = R._normal(key, (s["experts"], a, b), a ** -0.5)
    return w[s["lo"]:s["lo"] + s["held"]]


def _moe(key, s):
    d, ffe = s["d"], s["ffe"]
    ks = jax.random.split(key, 5)
    return {"router": _dense(ks[0], d, s["experts"]),
            "w1": _experts(ks[1], s, d, ffe), "w3": _experts(ks[2], s, d, ffe),
            "w2": _experts(ks[3], s, ffe, d),
            "shared": _mlp(ks[4], d, s["shared_ff"])}


def _layer(key, s, kind):
    """One layer's float32 weights by the program's key schedule: the
    attention on the layer key's first split, the MLP or the expert layer
    on its second. Norm scales are ones at initialisation."""
    ks = jax.random.split(key, 6)
    p = {"attn": _mla(ks[0], s)}
    if kind == "dense":
        p["mlp"] = _mlp(ks[1], s["d"], s["dense_ff"])
    else:
        p["moe"] = _moe(ks[1], s)
    return p


def init_weights(cfg: dict, seed) -> dict:
    """From ``seed`` (below 2**31), by the program's initialisation
    (normal, fan-in scaled, the same key schedule): the float32 embedding
    and head, and the keys each layer's weights are made from inside the
    forward's layer scan. Beside them ``yarn``, the rope's frequencies and
    scales from the file's ``rope_scaling`` (``yarn``), which the forward
    reads from here."""
    w = _init(R._frozen(cfg), jnp.asarray(seed, jnp.int32))
    inv, cs, scale = yarn(cfg)
    w["yarn"] = {"inv": jnp.asarray(inv), "cos_sin": jnp.float32(cs),
                 "softmax": jnp.float32(scale)}
    return w


@partial(jax.jit, static_argnums=(0,))
def _init(cfg_items, seed):
    s = model_shapes(dict(cfg_items))
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    vp = R.padded_vocab(s["vocab"])
    w = {"embed": R._normal(ks[0], (vp, s["d"]), s["d"] ** -0.5),
         "dense_keys": jax.random.split(ks[3], s["dense"]),
         "moe_keys": jax.random.split(ks[2], s["moe"])}
    if not s["tied"]:
        w["head"] = _dense(ks[1], s["d"], vp)
    return w


# ---------------------------------------------------------------------------
# reference: forward
# ---------------------------------------------------------------------------


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn(cfg: dict):
    """(inverse frequencies (rope/2,), cos and sin scale, softmax scale) of
    the published yarn rope (DeepseekV2YarnRotaryEmbedding and the
    attention's softmax_scale)."""
    rs = cfg["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"{cfg.get('name')}: the benchmark knows DeepSeek-V2"
                         " with yarn rope only")
    dim, base, factor = cfg["qk_rope_head_dim"], cfg["rope_theta"], \
        rs["factor"]

    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    freq_extra = 1.0 / base ** exponents
    freq_inter = 1.0 / (factor * base ** exponents)
    ramp = np.clip((np.arange(dim // 2) - low)
                   / ((high - low) or 0.001), 0.0, 1.0)
    extra_mask = 1.0 - ramp
    inv = freq_inter * (1.0 - extra_mask) + freq_extra * extra_mask
    cs = (_yarn_mscale(factor, rs["mscale"])
          / _yarn_mscale(factor, rs["mscale_all_dim"]))
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    if rs.get("mscale_all_dim"):
        scale *= _yarn_mscale(factor, rs["mscale_all_dim"]) ** 2
    return inv.astype(np.float32), cs, scale


def _rope(x, pos, inv, cs):
    """Rotate-half rope with yarn's frequencies; x (B, S, H, D)."""
    ang = pos[:, None].astype(jnp.float32) * inv
    cos = (jnp.cos(ang) * cs)[None, :, None]
    sin = (jnp.sin(ang) * cs)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _einsum(spec, x, w, fp8, w_axis):
    """A linear layer as an einsum; with ``fp8`` both operands in float8,
    the activation per row and the weight per slice along its contracted
    axis ``w_axis``."""
    if fp8:
        x, w = R._q8(x, -1), R._q8(w, w_axis)
    return jnp.einsum(spec, x, w)


def forward(cfg: dict, w: dict, tokens, fp8: bool = False):
    """Logits (B, S, vocab) of a causal pass over ``tokens`` (B, S); with
    ``fp8`` every linear layer's operands in float8 (the control)."""
    s = model_shapes(cfg)
    eps = cfg["rms_norm_eps"]
    B, S = tokens.shape
    H, nope, rd, r = s["h"], s["nope"], s["rope"], s["r"]
    inv, cs, scale = (w["yarn"][k] for k in ("inv", "cos_sin", "softmax"))
    pos = jnp.arange(S)
    emb = R._q8(w["embed"], -1) if fp8 else w["embed"]
    x = emb[tokens]
    causal = jnp.tril(jnp.ones((S, S), bool))

    def attention(x, p):
        # MLA in its expanded form: per head k = [W_uk c, k_rope], v = W_uv c
        h = R._rms(x, eps)
        q = R._linear(h, p["wq"], fp8).reshape(B, S, H, nope + rd)
        kv = R._linear(h, p["wdkv"], fp8)
        c = R._rms(kv[..., :r], eps)
        k_nope = _einsum("bsr,rhn->bshn", c, p["wuk"], fp8, 0)
        v = _einsum("bsr,rhv->bshv", c, p["wuv"], fp8, 0)
        q_rope = _rope(q[..., nope:], pos, inv, cs)
        k_rope = _rope(kv[:, :, None, r:], pos, inv, cs)
        q = jnp.concatenate([q[..., :nope], q_rope], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope, (B, S, H, rd))], -1)
        a = jnp.einsum("bshd,bthd->bhst", q, k) * scale
        a = jax.nn.softmax(jnp.where(causal, a, -1e30), axis=-1)
        o = jnp.einsum("bhst,bthv->bshv", a, v).reshape(B, S, H * s["vd"])
        return x + R._linear(o, p["wo"], fp8)

    def mlp(h, p):
        u = jax.nn.silu(R._linear(h, p["w1"], fp8)) * \
            R._linear(h, p["w3"], fp8)
        return R._linear(u, p["w2"], fp8)

    def experts(h, p):
        # softmax over every routed expert, greedy top-k; the held experts'
        # part of the weighted sum, each expert over the tokens routed to it
        t = h.reshape(B * S, s["d"])
        probs = jax.nn.softmax(R._linear(t, p["router"], fp8), axis=-1)
        top, idx = jax.lax.top_k(probs, s["k"])
        if cfg["norm_topk_prob"]:
            top = top / jnp.sum(top, -1, keepdims=True)
        top = top * cfg["routed_scaling_factor"]
        gate = sum(jax.nn.one_hot(idx[:, j] - s["lo"], s["held"]) *
                   top[:, j, None] for j in range(s["k"]))     # (T, held)
        u = jax.nn.silu(_einsum("td,edf->etf", t, p["w1"], fp8, 1)) * \
            _einsum("td,edf->etf", t, p["w3"], fp8, 1)
        y = _einsum("etf,efd->etd", u, p["w2"], fp8, 1)
        y = jnp.einsum("etd,te->td", y, gate) + mlp(t, p["shared"])
        return y.reshape(B, S, s["d"])

    def dense_layer(x, key):
        p = _layer(key, s, "dense")
        x = attention(x, p["attn"])
        return x + mlp(R._rms(x, eps), p["mlp"]), None

    def expert_layer(x, key):
        p = _layer(key, s, "moe")
        x = attention(x, p["attn"])
        return x + experts(R._rms(x, eps), p["moe"]), None

    x, _ = jax.lax.scan(dense_layer, x, w["dense_keys"])
    x, _ = jax.lax.scan(expert_layer, x, w["moe_keys"])
    x = R._rms(x, eps)
    head = w["embed"].T if s["tied"] else w["head"]
    return R._linear(x, head, fp8)[..., :s["vocab"]]
