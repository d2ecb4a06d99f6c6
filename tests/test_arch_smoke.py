"""Per-architecture smoke tests: reduced configs, one forward and one
prefill+decode step on CPU, asserting output shapes and no NaNs.

Also checks decode-vs-forward consistency: greedy prefill+decode logits must
match the full-sequence forward logits at the same positions. And the
serving engine, on every architecture it serves, answers as a plain B=1
loop of the model's own prefill and decode step would: its chunked decode
over shared slots, slot reuse across waves, and a dead slot decoding beside
a live one.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs, reduced
from repro.core.tokenizer import Tokenizer
from repro.models import model as M
from repro.serving.engine import SERVE_RUN, BatchScheduler, Engine, Request

ARCHS = [a for a in list_configs() if not a.startswith("storinfer-paper")]
RUN = M.RunCfg(attn_impl="naive", remat=False, moe_impl="scatter",
               q_block=16, kv_block=16)
# the engine prefills tokens alone: a VLM's prefill needs its mrope
# positions and an encoder-decoder's its audio frames
SERVE_ARCHS = [a for a in ARCHS
               if get_config(a).family not in ("vlm", "encdec")]


def make_batch(cfg, B=2, S=32, rng=None):
    rng = rng or np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                   jnp.int32)}
    batch["labels"] = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                                  jnp.int32)
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_seq, cfg.d_model)), jnp.float32
        ).astype(jnp.dtype(cfg.dtype))
    if cfg.rope_kind == "mrope":
        pos = np.broadcast_to(np.arange(S, dtype=np.int32), (3, B, S))
        batch["mrope_positions"] = jnp.asarray(pos)
    return batch


@functools.cache
def _setup(arch):
    cfg = reduced(get_config(arch))
    params = M.init_model(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


@pytest.fixture(scope="module", params=ARCHS)
def arch_setup(request):
    return _setup(request.param)


def test_forward_shapes_no_nan(arch_setup):
    cfg, params = arch_setup
    batch = make_batch(cfg)
    logits, aux = M.forward(cfg, params, batch, RUN)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), cfg.name
    assert not bool(jnp.isnan(aux["moe_aux"]).any())


def test_prefill_decode_matches_forward(arch_setup):
    cfg, params = arch_setup
    B, S = 2, 16
    batch = make_batch(cfg, B, S)
    # full forward over S tokens
    full_logits, _ = M.forward(cfg, params, batch, RUN)

    # prefill S-1 tokens, then decode token S-1; logits must match
    pre_batch = dict(batch)
    pre_batch["tokens"] = batch["tokens"][:, :S - 1]
    if "mrope_positions" in batch:
        pre_batch["mrope_positions"] = batch["mrope_positions"][:, :, :S - 1]
    pre_logits, cache = M.prefill(cfg, params, pre_batch, RUN, max_len=S + 4)
    np.testing.assert_allclose(
        np.asarray(pre_logits[:, 0]), np.asarray(full_logits[:, S - 2]),
        rtol=2e-2, atol=2e-2)

    logits, new_cache, _ = M.decode_step(
        cfg, params, batch["tokens"][:, S - 1:S], cache,
        jnp.asarray(S - 1, jnp.int32), RUN)
    np.testing.assert_allclose(
        np.asarray(logits[:, 0]), np.asarray(full_logits[:, S - 1]),
        rtol=2e-2, atol=2e-2)
    # cache shapes preserved
    for a, b in zip(jax.tree_util.tree_leaves(cache),
                    jax.tree_util.tree_leaves(new_cache)):
        assert a.shape == b.shape


def test_blockwise_matches_naive(arch_setup):
    cfg, params = arch_setup
    if cfg.family in ("ssm",):
        pytest.skip("attention-free")
    batch = make_batch(cfg, 2, 32)
    lo_naive, _ = M.forward(cfg, params, batch, RUN)
    lo_block, _ = M.forward(cfg, params, batch,
                            RUN.replace(attn_impl="blockwise"))
    np.testing.assert_allclose(np.asarray(lo_naive), np.asarray(lo_block),
                               rtol=2e-2, atol=2e-2)


def test_param_count_analytic_close(arch_setup):
    cfg, params = arch_setup
    actual = M.count_params(params)
    analytic = cfg.param_count()
    # analytic model ignores norms/bias/router-details: within 5%
    assert abs(actual - analytic) / max(actual, 1) < 0.05, \
        (cfg.name, actual, analytic)


# -- the serving engine against the model's own step ----------------------------

MAX_LEN, CHUNK = 32, 4
SHORT, LONG_A, LONG_B = ("who wrote hamlet", "what is the capital of france",
                         "when was the first moon landing")
# the engine's token at each step is within TOL of the reference's largest
# logit. Over the served architectures (CPU, f32) the largest gap was 0.0,
# and a two-slot step's logits differed from a lone one's by at most 5.5e-6;
# a decode started one position off, or a prefill written to the wrong
# slot, opened gaps of 0.76 and more in every test it changed
TOL = 1e-3


@functools.cache
def _serving(arch):
    """(engine, reference) over the architecture's shared params, held in
    f32 with an f32 cache. The reference replays a request's answer through
    a plain B=1 loop of ``M.prefill`` and ``M.decode_step`` at the engine's
    run and returns, at each step, the reference's largest logit less the
    logit of the engine's token."""
    cfg, params = _setup(arch)
    cfg = dataclasses.replace(cfg, dtype="float32")
    tok = Tokenizer.from_texts([SHORT, LONG_A, LONG_B])
    engine = Engine(cfg, params, tok, max_len=MAX_LEN, chunk=CHUNK)
    prefill = jax.jit(lambda p, t: M.prefill(cfg, p, {"tokens": t},
                                             SERVE_RUN, max_len=MAX_LEN))
    step = jax.jit(lambda p, t, c, n: M.decode_step(cfg, p, t, c, n,
                                                     SERVE_RUN)[:2])

    def gaps(req):
        ids = tok.encode(req.prompt, bos=True)
        logits, cache = prefill(params, jnp.asarray([ids], jnp.int32))
        out = []
        for i, t in enumerate(req.out_ids):
            lg = np.asarray(logits[0, -1], np.float64)
            out.append(lg.max() - lg[t])
            logits, cache = step(params, jnp.asarray([[t]], jnp.int32),
                                 cache, jnp.asarray(len(ids) + i, jnp.int32))
        return np.asarray(out)

    return engine, gaps


def _run(engine, reqs):
    sched = BatchScheduler(engine, batch_size=2)
    for r in reqs:
        sched.submit(r)
    sched.run_to_completion()
    assert all(r.done and not r.cancelled for r in reqs)
    return sched


@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_engine_decode_matches_model_step(arch):
    """Two slots, three prompts of two token lengths: the short prompt
    decodes alone in slot 0, then both long ones share the next wave, one
    of them in the reused slot 0. Every token of every answer is the
    greedy pick of the model's own step."""
    engine, gaps = _serving(arch)
    reqs = [Request(rid=0, prompt=SHORT, max_new=10),
            Request(rid=1, prompt=LONG_A, max_new=10),
            Request(rid=2, prompt=LONG_B, max_new=10)]
    sched = _run(engine, reqs)
    assert sched.waves == 2 and sched.slot_uses == [2, 1]
    for r in reqs:
        g = gaps(r)
        assert len(g) > CHUNK and g.max() <= TOL, (arch, r.rid, g)


@pytest.mark.parametrize("arch", SERVE_ARCHS)
def test_engine_dead_slot_leaves_live_answer(arch):
    """Two prompts of equal length share one wave; one retires after the
    first chunk and its slot decodes on, dead, beside the live one, whose
    answer stays the greedy pick of the model's own step."""
    engine, gaps = _serving(arch)
    reqs = [Request(rid=0, prompt=LONG_A, max_new=2),
            Request(rid=1, prompt=LONG_B, max_new=14)]
    sched = _run(engine, reqs)
    assert sched.waves == 1 and reqs[0].chunks == 1 and reqs[1].chunks > 2
    for r in reqs:
        g = gaps(r)
        assert g.max() <= TOL, (arch, r.rid, g)
