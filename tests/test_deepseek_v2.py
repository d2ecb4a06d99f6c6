"""DeepSeek-V2's mechanisms in the program, at a tiny width on the CPU:
yarn rope against the published formula, an expert layer that holds a
share of the routed experts and drops no token, and the decode chunk's
expert counter (absent from a model without experts)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.tokenizer import Tokenizer
from repro.models import layers as Lyr
from repro.models import mla as Mla
from repro.models import model as M
from repro.models import moe as Moe
from repro.serving.engine import SERVE_RUN, BatchScheduler, Engine, Request

V2_LITE = get_config("deepseek-v2-lite-16b")


def tiny(**kw):
    """DeepSeek-V2-Lite's layers at a test width: a leading dense layer and
    two expert layers of 16 routed experts, top-3, 2 shared; latent rank
    32, rope head 8."""
    base = dataclasses.replace(
        reduced(V2_LITE), n_experts=16, experts_per_tok=3, d_ff_expert=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, n_layers=3)
    return dataclasses.replace(base, **kw)


# -- yarn ---------------------------------------------------------------------

def _published_yarn(dim, base, factor, beta_fast, beta_slow, orig, mscale,
                    mscale_all_dim):
    """DeepseekV2YarnRotaryEmbedding and the attention's softmax_scale,
    transcribed in float64."""
    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2) / dim))
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = freq_inter * (1 - mask) + freq_extra * mask
    cos_sin = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    scale = (dim + 128) ** -0.5 * get_mscale(factor, mscale_all_dim) ** 2
    return inv, cos_sin, scale, (low, high)


def test_yarn_frequencies_and_scale_match_the_published_formula():
    c = V2_LITE
    assert c.rope_kind == "yarn" and c.rope_factor == 40
    inv, cos_sin, scale, corr = _published_yarn(
        64, 1e4, 40, 32, 1, 4096, 0.707, 0.707)
    assert corr == (10, 23)
    got = Lyr.yarn_freqs(64, c.rope_theta, c.rope_factor, c.yarn_beta_fast,
                         c.yarn_beta_slow, c.yarn_original_max_pos)
    np.testing.assert_allclose(got, inv, rtol=1e-6)
    theta = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:11], theta[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], theta[23:] / 40, rtol=1e-6)
    assert cos_sin == 1.0
    assert Mla.softmax_scale(c) == pytest.approx(scale, rel=1e-12)
    assert Mla.softmax_scale(c) == pytest.approx(192 ** -0.5 * 1.5896,
                                                 rel=1e-4)
    # the rotation itself, at positions up to 16,384: each pair of rotated
    # dims (i, i + 32) turned by position x frequency (float32 angles)
    pos = np.array([0, 1, 7, 4095, 4096, 9000, 16383, 16384], np.int32)
    x = np.random.default_rng(0).normal(size=(1, len(pos), 2, 64))
    out = np.asarray(Mla.rope(c, jnp.asarray(x, jnp.float32),
                              jnp.asarray(pos[None])), np.float64)
    ang = (pos[:, None].astype(np.float32) * got[None]).astype(np.float64)
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = x[..., :32], x[..., 32:]
    want = np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_yarn_leaves_the_standard_rope_alone():
    c = tiny(rope_kind="standard")
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 5, 2, 8)),
                    jnp.float32)
    pos = jnp.arange(5)[None]
    np.testing.assert_array_equal(np.asarray(Mla.rope(c, x, pos)),
                                  np.asarray(Lyr.apply_rope(x, pos, 1e4)))
    assert Mla.softmax_scale(c) == (16 + 8) ** -0.5


# -- the expert layer -----------------------------------------------------------

def test_expert_shares_add_up_to_the_uncut_layer():
    """Four chips' shares of 16 experts (4 each, offsets 0, 4, 8, 12): each
    holds the uncut layer's experts at its positions, and their routed
    parts, with the shared experts counted once, add up to the uncut
    layer's output."""
    whole = tiny()
    key = jax.random.PRNGKey(3)
    p = Moe.moe_init(key, whole, jnp.float32)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2, 9, 64)),
                    jnp.float32)
    y, routes = Moe.moe_ffn_dropless(whole, p, x)
    shared = Lyr.mlp(whole, p["shared"], x)
    total = shared
    for lo in (0, 4, 8, 12):
        part = dataclasses.replace(whole, n_experts_held=4, expert_offset=lo)
        ps = Moe.moe_init(key, part, jnp.float32)
        for name in ("w1", "w2", "w3"):
            np.testing.assert_array_equal(
                np.asarray(ps["experts"][name]),
                np.asarray(p["experts"][name][lo:lo + 4]))
        np.testing.assert_array_equal(np.asarray(ps["router"]["w"]),
                                      np.asarray(p["router"]["w"]))
        ys, rs = Moe.moe_ffn_dropless(part, ps, x)
        np.testing.assert_array_equal(np.asarray(rs), np.asarray(routes))
        total = total + (ys - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(y),
                               rtol=1e-5, atol=1e-5)


def test_routing_renormalises_only_under_norm_topk_prob():
    c = tiny()
    p = Moe.moe_init(jax.random.PRNGKey(4), c, jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(6, 64)),
                    jnp.float32)
    probs = jax.nn.softmax(x @ p["router"]["w"], -1)
    w, idx, _ = Moe.route(c, p, x)
    assert not c.norm_topk_prob
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(jnp.take_along_axis(probs, idx, -1)),
        rtol=1e-5)
    w2, _, _ = Moe.route(dataclasses.replace(c, norm_topk_prob=True,
                                             routed_scaling_factor=2.0), p, x)
    np.testing.assert_allclose(np.asarray(w2.sum(-1)), 2.0, rtol=1e-5)


def test_prefill_drops_no_token():
    """The dropless layer gives each token of a 40-token prefill what it
    gives that token alone, where the capacity path (9 slots an expert
    here) drops some of them."""
    c = tiny(moe_capacity_factor=1.25)
    p = Moe.moe_init(jax.random.PRNGKey(5), c, jnp.float32)
    x = jnp.asarray(np.random.default_rng(4).normal(size=(1, 40, 64)),
                    jnp.float32)
    y, routes = Moe.moe_ffn_dropless(c, p, x)
    alone = jnp.concatenate([Moe.moe_ffn_dropless(c, p, x[:, i:i + 1])[0]
                             for i in range(40)], axis=1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(alone), rtol=1e-5,
                               atol=1e-6)
    counts = np.bincount(np.asarray(routes).ravel(), minlength=16)
    assert counts.max() > Moe.capacity(c, 40)
    capped, _ = Moe.moe_ffn(c, p, x)
    assert float(jnp.abs(capped - y).max()) > 1e-3


def _live_slot_logits(c, run, copies):
    """The logits of the last of 16 decode slots, the one live slot, with
    the 15 dead slots holding random tokens and cache, or with ``copies``
    of the live slot's own token and cache."""
    params = M.init_model(jax.random.PRNGKey(6), c, dtype=jnp.float32)
    B, L = 16, 16
    rng = np.random.default_rng(5)
    prompt = jnp.asarray(rng.integers(4, 500, (1, 7)), jnp.int32)
    _, one = M.prefill(c, params, {"tokens": prompt}, run, max_len=L)
    cache = jax.tree_util.tree_map(
        lambda o: jnp.asarray(rng.normal(size=(o.shape[0], B) + o.shape[2:]),
                              o.dtype), one)
    toks = jnp.asarray(rng.integers(4, 500, (B, 1)), jnp.int32)
    if copies:
        cache = jax.tree_util.tree_map(
            lambda o: jnp.broadcast_to(o, (o.shape[0], B) + o.shape[2:]), one)
        toks = jnp.full((B, 1), 11, jnp.int32)
    cache = jax.tree_util.tree_map(lambda b, o: b.at[:, B - 1:].set(o),
                                   cache, one)
    toks = toks.at[B - 1, 0].set(11)
    logits, _, _ = M.decode_step(c, params, toks, cache,
                                 jnp.asarray(7, jnp.int32), run)
    return np.asarray(logits[B - 1, 0])


def test_a_live_slot_does_not_see_dead_slots_tokens():
    """A decode step's logits for a live slot are the same whatever tokens
    and cache the dead slots hold: the dropless layer shares no expert
    capacity. Under the capacity path (8 slots an expert) dead slots that
    route as the live one does push it out of its experts."""
    c = tiny(moe_capacity_factor=1.25)
    for held in (c, dataclasses.replace(c, n_experts_held=8,
                                        expert_offset=8)):
        np.testing.assert_allclose(
            _live_slot_logits(held, SERVE_RUN, copies=True),
            _live_slot_logits(held, SERVE_RUN, copies=False),
            rtol=1e-6, atol=1e-6)
    capped = SERVE_RUN.replace(moe_impl="scatter")
    gap = np.abs(_live_slot_logits(c, capped, copies=True)
                 - _live_slot_logits(c, capped, copies=False)).max()
    assert gap > 1e-3


# -- the decode chunk's expert counter ---------------------------------------------

def _engine(cfg):
    tok = Tokenizer.from_texts(["where is the old mill and the bridge"])
    cfg = dataclasses.replace(cfg, vocab_size=tok.vocab_size)
    params = M.init_model(jax.random.PRNGKey(0), cfg)
    return Engine(cfg, params, tok, max_len=32, chunk=2)


def _chunk(e, B, live):
    cache = M.init_cache(e.cfg, B, e.max_len)
    return e._decode_chunk(e.params, jnp.zeros((B, 1), jnp.int32), cache,
                           jnp.asarray(3, jnp.int32), jax.random.PRNGKey(0),
                           None, jnp.asarray(live))


def test_qwen3_decode_chunk_returns_no_expert_output():
    e = _engine(reduced(get_config("qwen3-1.7b")))
    assert len(_chunk(e, 2, [True, False])) == 4


def test_expert_decode_chunk_counts_held_experts_live_slots_touch():
    e = _engine(tiny(n_experts_held=4, expert_offset=4))
    out = _chunk(e, 3, [True, True, False])
    assert len(out) == 5
    touched, layer_steps, read = np.asarray(out[4]).tolist()
    assert layer_steps == 2 * e.chunk          # 2 expert layers x 2 steps
    assert read == 4 * layer_steps             # every held expert, each step
    assert 0 <= touched <= read
    _, _, _, _, none_live = _chunk(e, 3, [False, False, False])
    assert np.asarray(none_live).tolist() == [0, 0, 0]


def test_scheduler_sums_the_expert_counter_over_its_chunks():
    e = _engine(tiny(n_experts_held=8))
    sched = BatchScheduler(e, batch_size=2)
    for i, prompt in enumerate(["where is the mill", "where is the old"]):
        sched.submit(Request(rid=i, prompt=prompt, max_new=5))
    done = sched.run_to_completion()
    assert len(done) == 2
    chunks = max(r.chunks for r in done)
    assert sched.moe_layer_steps == 2 * e.chunk * chunks
    assert sched.experts_read == 8 * sched.moe_layer_steps
    assert 0 < sched.experts_touched <= sched.experts_read
