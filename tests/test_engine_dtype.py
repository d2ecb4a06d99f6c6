"""The engine holds its params at the configuration's dtype (bf16) and
keeps the residual stream in f32: every matmul takes a bf16 weight as it
is, with its activation cast to bf16 and the product accumulated in f32.
No program of the engine converts a weight matrix to f32, and its logits
agree with the full-sequence forward of the same params."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.extend.core import Literal

from repro.api import EngineCfg, _build_engine
from repro.core.tokenizer import Tokenizer
from repro.models import layers as Lyr
from repro.models import model as M

# one architecture per family the engine serves: dense GQA (qk-norm, tied
# head), dense with q/k/v bias, MoE over GQA, MLA + MoE with a leading
# dense layer, hybrid (mamba + shared attention), pure SSM
ARCHS = ["qwen3-1.7b", "starcoder2-7b", "grok-1-314b",
         "deepseek-v2-lite-16b", "zamba2-1.2b", "mamba2-130m"]
PROMPT = "where is the river that runs past the old mill and the bridge"
# params the model keeps in f32 whatever the configuration's dtype
F32_BY_DESIGN = {"router", "A_log", "dt_bias", "D"}

# ops that only select or lay out a weight: their result is still the
# weight, as far as a convert of it is concerned (a ``gather`` is not: an
# embedding lookup reads rows, and its rows join the stream in f32)
_VIEWS = {"slice", "dynamic_slice", "squeeze", "reshape", "transpose",
          "copy", "copy_p", "expand_dims"}


def _sub_jaxprs(eqn):
    """(sub-jaxpr, its invars' positions among the eqn's invars, whether its
    outvars are the eqn's outvars) for each jaxpr an eqn calls."""
    p = eqn.params
    name = eqn.primitive.name
    if name == "while":
        nc, nb = p["cond_nconsts"], p["body_nconsts"]
        n = len(eqn.invars)
        yield p["body_jaxpr"].jaxpr, list(range(nc, n)), False
        yield p["cond_jaxpr"].jaxpr, list(range(nc)) + list(
            range(nc + nb, n)), False
        return
    if name == "cond":
        for br in p["branches"]:
            yield br.jaxpr, list(range(1, len(eqn.invars))), False
        return
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = p.get(key)
        if sub is None:
            continue
        sub = getattr(sub, "jaxpr", sub)
        if len(sub.invars) == len(eqn.invars):
            yield sub, list(range(len(eqn.invars))), name != "scan"


def weight_upcasts(jaxpr, weights):
    """Converts bf16 -> f32 of a weight matrix (two or more dims) in
    ``jaxpr``, and matmuls of a bf16 weight against an f32 operand (which
    lowering converts), following each weight through views and into every
    jaxpr it is passed to. ``weights`` is the set of the jaxpr's weight invars.
    Returns the offending operands' shapes."""
    found = []
    tainted = set(weights)
    for eqn in jaxpr.eqns:
        ins = [v for v in eqn.invars if not isinstance(v, Literal)]
        hit = any(v in tainted for v in ins)
        if not hit:
            continue
        name = eqn.primitive.name
        if name == "convert_element_type":
            src = eqn.invars[0].aval
            if (src.dtype == jnp.bfloat16 and src.ndim >= 2
                    and eqn.params["new_dtype"] == jnp.float32):
                found.append(tuple(src.shape))
            continue
        if name == "dot_general":
            # a bf16 weight against an f32 operand: lowering converts it
            a, b = (v.aval for v in eqn.invars)
            for w, other in ((eqn.invars[0], b), (eqn.invars[1], a)):
                if (w in tainted and w.aval.dtype == jnp.bfloat16
                        and other.dtype == jnp.float32):
                    found.append(tuple(w.aval.shape))
            continue
        if name in _VIEWS and eqn.invars[0] in tainted:
            tainted.update(eqn.outvars)
            continue
        for sub, pos, passes_out in _sub_jaxprs(eqn):
            sub_w = {sub.invars[i] for i, j in enumerate(pos)
                     if not isinstance(eqn.invars[j], Literal)
                     and eqn.invars[j] in tainted}
            if not sub_w:
                continue
            found += weight_upcasts(sub, sub_w)
            if passes_out:
                tainted.update(o for o, so in zip(eqn.outvars, sub.outvars)
                               if so in sub_w)
    return found


def _program_upcasts(fn, params, *args):
    closed = jax.make_jaxpr(fn)(params, *args)
    n = len(jax.tree_util.tree_leaves(params))
    weights = {v for v in closed.jaxpr.invars[:n]
               if v.aval.dtype == jnp.bfloat16}
    assert weights
    return weight_upcasts(closed.jaxpr, weights)


@pytest.fixture(scope="module", params=ARCHS)
def engine(request):
    tok = Tokenizer.from_texts([PROMPT])
    return _build_engine(EngineCfg(arch=request.param, max_len=32, chunk=2),
                         tok)


def test_engine_holds_params_at_config_dtype(engine):
    dtype = jnp.dtype(engine.cfg.dtype)
    assert dtype == jnp.bfloat16
    leaves = jax.tree_util.tree_leaves_with_path(engine.params)
    for path, leaf in leaves:
        keys = {getattr(k, "key", None) for k in path}
        want = jnp.float32 if keys & F32_BY_DESIGN else dtype
        assert leaf.dtype == want, (jax.tree_util.keystr(path), leaf.dtype)
    if engine.cfg.family == "dense":
        assert {leaf.dtype for _, leaf in leaves} == {dtype}


def test_engine_programs_take_bf16_weights_as_they_are(engine):
    ids = engine.tok.encode(PROMPT, bos=True)
    tokens = jnp.asarray([ids], jnp.int32)
    assert _program_upcasts(engine._prefill_impl, engine.params,
                            tokens) == []
    B = 2
    cache = M.init_cache(engine.cfg, B, engine.max_len)
    assert _program_upcasts(
        engine._decode_chunk_impl, engine.params,
        jnp.zeros((B, 1), jnp.int32), cache, jnp.asarray(3, jnp.int32),
        jax.random.PRNGKey(0), None, jnp.ones((B,), bool)) == []


def test_upcast_finder_sees_a_promoted_weight():
    """The finder is not vacuous: jnp's promotion of a bf16 weight in an
    f32 matmul, inside a scan over a layer stack, is found."""
    def f(ws, x):
        return jax.lax.scan(lambda x, w: (x @ w, None), x, ws)[0]

    ws = jnp.ones((3, 8, 8), jnp.bfloat16)
    closed = jax.make_jaxpr(f)(ws, jnp.ones((2, 8), jnp.float32))
    assert weight_upcasts(closed.jaxpr, {closed.jaxpr.invars[0]}) == [(8, 8)]
    closed = jax.make_jaxpr(lambda ws, x: jax.lax.scan(
        lambda x, w: (Lyr.dense({"w": w}, x), None), x, ws)[0])(
        ws, jnp.ones((2, 8), jnp.float32))
    assert weight_upcasts(closed.jaxpr, {closed.jaxpr.invars[0]}) == []


def test_dense_bf16_weight_takes_activation_in_bf16():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 5, 64)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(64, 48)) / 8, jnp.bfloat16)
    y = Lyr.dense({"w": w}, x)
    assert y.dtype == jnp.float32
    want = jnp.matmul(x.astype(jnp.bfloat16), w,
                      preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    # the product of the bf16-rounded operands, summed in f32
    exact = np.asarray(x.astype(jnp.bfloat16), np.float64) @ np.asarray(
        w, np.float64)
    np.testing.assert_allclose(np.asarray(y), exact, rtol=1e-5, atol=1e-5)
    # same dtypes: jnp's matmul as before
    xb = x.astype(jnp.bfloat16)
    yb = Lyr.dense({"w": w}, xb)
    assert yb.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(yb, np.float32),
                                  np.asarray(xb @ w, np.float32))


def test_bf16_engine_logits_match_forward(engine):
    """Prefill and first decode step of the bf16-held engine, the step
    reading its cache from the engine's bf16 batch cache, against the
    full-sequence forward. The prefill runs the forward's own code: under
    the engine's run it agrees to 1e-5 of the largest logit. Against the
    f32 forward at highest precision of the same (bf16-valued) weights,
    both rows agree to 3e-2: the activations and the cache are rounded to
    bf16 (largest seen on the CPU: 1.5e-2, the hybrid's decode step)."""
    e = engine
    ids = e.tok.encode(PROMPT, bos=True)
    logits, one = e._prefill(e.params, jnp.asarray([ids], jnp.int32))
    cache = e._write_slot(M.init_cache(e.cfg, 1, e.max_len), one,
                          jnp.asarray(0, jnp.int32))
    nxt = int(jnp.argmax(logits[0, -1]))
    step, _, _ = jax.jit(lambda p, t, c, n: M.decode_step(
        e.cfg, p, t, c, n, e.run))(
        e.params, jnp.asarray([[nxt]], jnp.int32), cache,
        jnp.asarray(len(ids), jnp.int32))
    got = np.stack([np.asarray(logits[0, -1], np.float32),
                    np.asarray(step[0, -1], np.float32)])
    seq = jnp.asarray([ids + [nxt]], jnp.int32)
    V = e.cfg.vocab_size

    def rel(want):
        """Per row: largest gap over the largest logit."""
        want = np.asarray(want[0], np.float32)[len(ids) - 1:, :V]
        return np.abs(got[:, :V] - want).max(-1) / np.abs(want).max()

    same = jax.jit(lambda p, t: M.forward(e.cfg, p, {"tokens": t},
                                          e.run)[0])(e.params, seq)
    assert rel(same)[0] < 1e-5
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), e.params)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: M.forward(e.cfg, p, {"tokens": t},
                                             M.RunCfg(attn_impl="naive",
                                                      remat=False))[0])(
            f32, seq)
    assert rel(ref).max() < 3e-2
