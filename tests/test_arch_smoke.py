"""Per-architecture smoke tests: reduced configs, one forward / train-grad /
prefill+decode step on CPU, asserting output shapes and no NaNs.

Also checks decode-vs-forward consistency: greedy prefill+decode logits must
match the full-sequence forward logits at the same positions.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs, reduced, SHAPES
from repro.models import model as M

ARCHS = [a for a in list_configs() if not a.startswith("storinfer-paper")]
RUN = M.RunCfg(attn_impl="naive", remat=False, scan_layers=True,
               moe_impl="scatter", q_block=16, kv_block=16)


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


@pytest.fixture(scope="module", params=ARCHS)
def arch_setup(request):
    cfg = reduced(get_config(request.param))
    params = M.init_model(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def test_forward_shapes_no_nan(arch_setup):
    cfg, params = arch_setup
    batch = make_batch(cfg)
    logits, aux = M.forward(cfg, params, batch, RUN)
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert not bool(jnp.isnan(logits).any()), cfg.name
    assert not bool(jnp.isnan(aux["moe_aux"]).any())


def test_train_grad_step(arch_setup):
    cfg, params = arch_setup
    batch = make_batch(cfg)
    loss, grads = jax.value_and_grad(
        lambda p: M.lm_loss(cfg, p, batch, RUN)[0])(params)
    assert np.isfinite(float(loss)), cfg.name
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree_util.tree_leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, cfg.name


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
