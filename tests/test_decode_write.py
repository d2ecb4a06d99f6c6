"""The decode step writes one KV position a step. Each attention layer reads
its cache slice as given and returns the step's row; one write per cache
leaf places all layers' rows at cache_len, dead slots' included, with no
masked rewrite of the cache. Checked against the whole-layer write and
masked rewrite it replaces (equal numbers for live slots), by the decode
chunk's jaxpr (no whole-cache select, no per-layer cache write, one row
write a step per leaf), and by a slot reused after an early retirement."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.configs import get_config, reduced
from repro.core.tokenizer import Tokenizer
from repro.models import layers as Lyr
from repro.models import mla as Mla
from repro.models import model as M
from repro.serving.engine import SERVE_RUN, BatchScheduler, Engine, Request

ARCHS = ["qwen3-1.7b", "deepseek-v2-lite-16b"]
MAX_LEN, CHUNK, START = 16, 4, 5
LIVE = [True, False, True]


def _cfg(arch, dtype="float32"):
    return dataclasses.replace(reduced(get_config(arch)), dtype=dtype)


# -- the formula the row write replaces ---------------------------------------

def _write(cache, new, idx):
    """Write ``new`` (B,1,...) at position idx of a layer cache (B,M,...)."""
    return jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype), (0, idx) + (0,) * (cache.ndim - 2))


def _attend(cfg, a, h, layer, clen):
    """One attention layer the old way: write the step's key and value into
    the whole layer cache, then attend over positions <= clen. Returns
    (out, {leaf: the updated layer cache})."""
    B, f32 = h.shape[0], jnp.float32
    pos = jnp.full((B, 1), clen, jnp.int32)
    if cfg.use_mla:
        qn, qr = Mla._project_q(cfg, a, h)
        qr = Mla.rope(cfg, qr, pos)
        ckv, kr = Mla._project_ckv(cfg, a, h, pos)
        ckv_c = _write(layer["ckv"], ckv, clen)
        kr_c = _write(layer["krope"], kr[:, :, 0, :], clen)
        q_c = Lyr.weight_einsum("bshn,rhn->bshr", qn, a["wuk"])
        s = (jnp.einsum("bshr,btr->bhst", q_c, ckv_c)
             + jnp.einsum("bshr,btr->bhst", qr, kr_c)).astype(f32)
        s = jnp.where(jnp.arange(s.shape[-1]) <= clen,
                      s * Mla.softmax_scale(cfg), -1e30)
        pr = jax.nn.softmax(s, -1).astype(ckv_c.dtype)
        o = jnp.einsum("bhst,btr->bshr", pr, ckv_c)
        o = Lyr.weight_einsum("bshr,rhv->bshv", o, a["wuv"])
        return (Lyr.dense(a["wo"], o.reshape(B, 1, -1)),
                {"ckv": ckv_c, "krope": kr_c})
    hd, Hq, Hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    q = Lyr.dense(a["wq"], h).reshape(B, 1, Hq, hd)
    k = Lyr.dense(a["wk"], h).reshape(B, 1, Hkv, hd)
    v = Lyr.dense(a["wv"], h).reshape(B, 1, Hkv, hd)
    q, k = M._rope_q_k(cfg, a, q, k, pos, None)
    kc = _write(layer["k"], k, clen)
    vc = _write(layer["v"], v, clen)
    s = jnp.einsum("bkgd,btkd->bkgt", q.reshape(B, Hkv, Hq // Hkv, hd),
                   kc).astype(f32)
    s = jnp.where(jnp.arange(s.shape[-1]) <= clen, s * hd ** -0.5, -1e30)
    pr = jax.nn.softmax(s, -1).astype(vc.dtype)
    o = jnp.einsum("bkgt,btkv->bkgv", pr, vc)
    return Lyr.dense(a["wo"], o.reshape(B, 1, -1)), {"k": kc, "v": vc}


def _old_step(cfg, params, tok, cache, clen):
    """A decode step layer by layer, each layer writing its whole cache."""
    run = SERVE_RUN
    x = M.embed_tokens(cfg, params, tok, run.stream_dtype)
    n0 = cfg.first_dense_layers if cfg.family == "moe" else 0
    new = {name: [] for name in cache}
    for i in range(cfg.n_layers):
        stack, j = (params["dense0"], i) if i < n0 else (params["blocks"],
                                                         i - n0)
        lp = jax.tree_util.tree_map(lambda a: a[j], stack)
        layer = {name: c[i] for name, c in cache.items()}
        out, written = _attend(cfg, lp["attn"],
                               Lyr.rmsnorm(lp["ln1"], x, cfg.norm_eps),
                               layer, clen)
        for name, c in written.items():
            new[name].append(c)
        x = x + out
        hn = Lyr.rmsnorm(lp["ln2"], x, cfg.norm_eps)
        x = x + (M.apply_moe(cfg, run, lp["moe"], hn)[0] if "moe" in lp
                 else Lyr.mlp(cfg, lp["mlp"], hn))
    return (M.lm_logits(cfg, run, params, x),
            {name: jnp.stack(c) for name, c in new.items()})


def _old_chunk(cfg, params, tok, cache, clen, live):
    """The chunk the old way: every step's whole new cache masked by
    ``where(live, new, old)``."""
    toks = []
    for _ in range(CHUNK):
        logits, new = _old_step(cfg, params, tok, cache, clen)
        nxt = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        tok = jnp.where(live[:, None], nxt, tok)
        cache = jax.tree_util.tree_map(
            lambda n, o: jnp.where(
                jnp.reshape(live, (1, -1) + (1,) * (n.ndim - 2)), n, o),
            new, cache)
        clen = clen + 1
        toks.append(tok[:, 0])
    return tok, cache, clen, jnp.stack(toks, 1)


def _state(cfg, seed=0):
    B = len(LIVE)
    params = M.init_model(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    cache = jax.tree_util.tree_map(
        lambda s: jnp.asarray(rng.normal(size=s.shape), s.dtype),
        M.cache_struct(cfg, B, MAX_LEN))
    tok = jnp.asarray(rng.integers(4, cfg.vocab_size, (B, 1)), jnp.int32)
    return params, tok, cache, jnp.asarray(START, jnp.int32)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_chunk_matches_the_masked_whole_cache_write(arch):
    cfg = _cfg(arch)
    params, tok, cache, clen = _state(cfg)
    live = jnp.asarray(LIVE)
    e = Engine(cfg, params, Tokenizer(["x"]), max_len=MAX_LEN, chunk=CHUNK)
    got = e._decode_chunk(params, tok, cache, clen, jax.random.PRNGKey(0),
                          None, live)
    want = _old_chunk(cfg, params, tok, cache, clen, live)
    assert int(got[2]) == int(want[2]) == START + CHUNK
    rows = np.flatnonzero(LIVE)
    np.testing.assert_array_equal(np.asarray(got[3])[rows],
                                  np.asarray(want[3])[rows])
    np.testing.assert_array_equal(np.asarray(got[0])[rows],
                                  np.asarray(want[0])[rows])
    dead = np.flatnonzero(~np.asarray(LIVE))
    assert (np.asarray(got[3])[dead] == np.asarray(tok)[dead]).all()
    for name in cache:
        np.testing.assert_allclose(np.asarray(got[1][name])[:, rows],
                                   np.asarray(want[1][name])[:, rows],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_returns_the_whole_updated_cache(arch):
    cfg = _cfg(arch)
    params, tok, cache, clen = _state(cfg, seed=1)
    logits, new, _ = jax.jit(
        lambda p, t, c, n: M.decode_step(cfg, p, t, c, n, SERVE_RUN))(
            params, tok, cache, clen)
    want_logits, want = _old_step(cfg, params, tok, cache, clen)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=1e-5, atol=1e-5)
    assert set(new) == set(want)
    for name in want:
        np.testing.assert_allclose(np.asarray(new[name]),
                                   np.asarray(want[name]),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_in_bf16_matches_the_whole_layer_write(arch):
    """At the stated bf16 the row write rounds as the old write did: the
    value sum over the past and the step's own row is rounded once to the
    cache's dtype. On the CPU the two agreed exactly on 6 seeds of each
    model, where the old formula's own bf16 rounding, against f32, moved
    the logits by 0.019-0.43; the limits are a quarter of the least."""
    cfg = _cfg(arch, dtype="bfloat16")
    params, tok, cache, clen = _state(cfg, seed=3)
    logits, new, _ = jax.jit(
        lambda p, t, c, n: M.decode_step(cfg, p, t, c, n, SERVE_RUN))(
            params, tok, cache, clen)
    want_logits, want = _old_step(cfg, params, tok, cache, clen)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want_logits),
                               rtol=0, atol=5e-3)
    for name in want:
        assert new[name].dtype == want[name].dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(new[name], np.float32),
                                   np.asarray(want[name], np.float32),
                                   rtol=0, atol=5e-3)


# -- the chunk's jaxpr ----------------------------------------------------------

def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                if isinstance(sub, ClosedJaxpr):
                    yield from _eqns(sub.jaxpr)
                elif isinstance(sub, Jaxpr):
                    yield from _eqns(sub)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_chunk_writes_one_row_a_step_per_cache_leaf(arch):
    cfg = _cfg(arch, dtype="bfloat16")
    B = len(LIVE)
    e = Engine(cfg, None, Tokenizer(["x"]), max_len=MAX_LEN, chunk=CHUNK)
    sds = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0), cfg))
    cache = M.cache_struct(cfg, B, MAX_LEN)
    jaxpr = jax.make_jaxpr(e._decode_chunk_impl)(
        params, sds((B, 1), jnp.int32), cache, sds((), jnp.int32),
        sds((2,), jnp.uint32), None, sds((B,), jnp.bool_)).jaxpr
    whole = {tuple(c.shape) for c in cache.values()}
    layer = {s[1:] for s in whole}
    selects, layer_writes, row_writes = [], [], {}
    for eqn in _eqns(jaxpr):
        name = eqn.primitive.name
        shape = tuple(eqn.outvars[0].aval.shape)
        if name == "select_n" and shape in whole:
            selects.append(shape)
        if name == "dynamic_update_slice":
            if shape in layer:
                layer_writes.append(shape)
            if shape in whole:
                upd = tuple(eqn.invars[1].aval.shape)
                assert upd == shape[:2] + (1,) + shape[3:]
                row_writes[shape] = row_writes.get(shape, 0) + 1
    assert selects == []
    assert layer_writes == []
    leaves = {}
    for c in cache.values():
        leaves[tuple(c.shape)] = leaves.get(tuple(c.shape), 0) + 1
    assert row_writes == leaves


# -- a slot reused after an early retirement ------------------------------------

def test_slot_reused_after_early_retirement_answers_as_a_lone_session():
    """Slot 0 retires after one chunk and decodes on, dead, for the rest of
    its wave, writing rows past its prompt; a shorter prompt then takes
    the slot and answers as it would alone."""
    short, long_, later = "hello world what is", "hello world what was", \
        "tell me"
    tok = Tokenizer.from_texts([short, long_, later])
    cfg = dataclasses.replace(reduced(get_config("qwen3-1.7b")),
                              vocab_size=tok.vocab_size, n_layers=2)
    params = M.init_model(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    e = Engine(cfg, params, tok, max_len=32, chunk=CHUNK)
    sched = BatchScheduler(e, batch_size=2)
    reqs = [Request(rid=0, prompt=short, max_new=2),
            Request(rid=1, prompt=long_, max_new=14),
            Request(rid=2, prompt=later, max_new=10)]
    for r in reqs:
        sched.submit(r)
    sched.run_to_completion()
    assert all(r.done for r in reqs)
    assert reqs[0].slot == reqs[2].slot == 0 and reqs[0].chunks == 1
    assert reqs[1].chunks > 1 and sched.waves == 2
    s = e.start_session(later, max_new=10)
    while not s.done:
        s.step_chunk()
    assert reqs[2].out_ids == s.out_ids
