"""Multi-device distributed checks, run under 8 forced host devices.

Executed by tests/test_distributed.py via subprocess (the main pytest
process must keep seeing 1 device — the dry-run is the only other place the
device count is forced). Asserts:

  1. sharded MIPS top-k == flat reference on a (data=2, model=4) mesh
  2. seq-sharded GQA decode == naive decode attention
  3. seq-sharded MLA decode == naive absorbed decode; decode_step on the
     seq-sharded path == the unsharded one, and its row write moves no
     part of the cache between devices
  4. EP (all-to-all) MoE == local scatter MoE, forward AND gradients
  5. param sharding rules produce valid NamedShardings for all 10 archs
  6. elastic re-shard: checkpoint saved from one mesh restores onto another
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, list_configs, reduced
from repro.core.index import FlatIndex
from repro.distributed import sharding as Sh
from repro.distributed.topk import sharded_mips_topk
from repro.launch.mesh import make_local_mesh, make_mesh
from repro.models import model as M
from repro.models import moe as Moe


def check_sharded_topk(mesh):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 32)).astype(np.float32)
    q = rng.normal(size=(5, 32)).astype(np.float32)
    v, i = sharded_mips_topk(jnp.asarray(q), jnp.asarray(x), 7, mesh=mesh)
    vr, ir = FlatIndex(x).search(q, 7)
    np.testing.assert_allclose(np.asarray(v), vr, rtol=1e-5, atol=1e-5)
    sel = np.take_along_axis(q @ x.T, np.asarray(i), axis=1)
    np.testing.assert_allclose(sel, vr, rtol=1e-5, atol=1e-5)
    print("ok sharded_topk")


def check_seq_sharded_gqa(mesh):
    from repro.distributed.decode_attn import gqa_decode_seq_sharded
    rng = np.random.default_rng(1)
    B, M_, Hq, Hkv, D = 4, 32, 4, 2, 8
    q = jnp.asarray(rng.normal(size=(B, 1, Hq, D)).astype(np.float32))
    k_new = jnp.asarray(rng.normal(size=(B, 1, Hkv, D)).astype(np.float32))
    v_new = jnp.asarray(rng.normal(size=(B, 1, Hkv, D)).astype(np.float32))
    kc = jnp.asarray(rng.normal(size=(B, M_, Hkv, D)).astype(np.float32))
    vc = jnp.asarray(rng.normal(size=(B, M_, Hkv, D)).astype(np.float32))
    for pos in (0, 9, M_ - 1):
        cache_len = jnp.asarray(pos, jnp.int32)
        out = gqa_decode_seq_sharded(q, k_new, v_new, kc, vc, cache_len,
                                     mesh=mesh, batch_axes=("data",))
        # naive reference: write the row, attend over positions <= pos
        kc_ref = jax.lax.dynamic_update_slice(kc, k_new, (0, pos, 0, 0))
        vc_ref = jax.lax.dynamic_update_slice(vc, v_new, (0, pos, 0, 0))
        G = Hq // Hkv
        qg = q.reshape(B, Hkv, G, D)
        s = jnp.einsum("bkgd,btkd->bkgt", qg, kc_ref) * (D ** -0.5)
        mask = jnp.arange(M_) <= pos
        s = jnp.where(mask[None, None, None], s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, -1)
        o_ref = jnp.einsum("bkgt,btkv->bkgv", p, vc_ref).reshape(
            B, 1, Hq * D)
        np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                                   rtol=1e-4, atol=1e-4)
    print("ok seq_sharded_gqa")


def check_seq_sharded_mla(mesh):
    from repro.distributed.decode_attn import mla_decode_seq_sharded
    rng = np.random.default_rng(2)
    B, M_, H, r, dr = 4, 32, 4, 16, 8
    f = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    q_c, q_r = f(B, 1, H, r), f(B, 1, H, dr)
    ckv_new, krope_new = f(B, 1, r), f(B, 1, dr)
    ckv_c, krope_c = f(B, M_, r), f(B, M_, dr)
    scale = 0.3
    for pos in (0, 9, M_ - 1):
        out = mla_decode_seq_sharded(q_c, q_r, ckv_new, krope_new, ckv_c,
                                     krope_c, jnp.asarray(pos, jnp.int32),
                                     scale, mesh=mesh, batch_axes=("data",))
        ckv = jax.lax.dynamic_update_slice(ckv_c, ckv_new, (0, pos, 0))
        kr = jax.lax.dynamic_update_slice(krope_c, krope_new, (0, pos, 0))
        s = (jnp.einsum("bshr,btr->bhst", q_c, ckv)
             + jnp.einsum("bshr,btr->bhst", q_r, kr)) * scale
        s = jnp.where(jnp.arange(M_) <= pos, s, -1e30)
        o_ref = jnp.einsum("bhst,btr->bshr", jax.nn.softmax(s, -1), ckv)
        np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                                   rtol=1e-4, atol=1e-4)
    print("ok seq_sharded_mla")


def _collectives(hlo):
    """[(op, elements of its largest result)] of the compiled collectives."""
    out = []
    for m in re.finditer(r"= (.*?) (all-gather|all-to-all|collective-permute"
                         r"|all-reduce|reduce-scatter)\(", hlo):
        dims = re.findall(r"\[([\d,]*)\]", m.group(1))
        out.append((m.group(2), max(int(np.prod([int(d) for d in ds.split(",")
                                                  if d]))
                                    for ds in dims)))
    return out


def check_seq_sharded_decode_step():
    """decode_step on the sequence-sharded path equals the unsharded one,
    and the row write moves no part of the cache between devices: besides
    the attention combine's all-reduces, no collective moves as much as
    one device's shard of a cache leaf (the expert router gathers its
    (B, E) scores)."""
    from repro.serving.engine import SERVE_RUN
    mesh = make_local_mesh(model=4, data=2)   # auto axes, as a launch has
    B, M_ = 4, 32
    for arch in ("qwen3-1.7b", "deepseek-v2-lite-16b"):
        cfg = dataclasses.replace(reduced(get_config(arch)), dtype="float32")
        params = M.init_model(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(4)
        cache = jax.tree_util.tree_map(
            lambda s: jnp.asarray(rng.normal(size=s.shape), s.dtype),
            M.cache_struct(cfg, B, M_))
        tok = jnp.asarray(rng.integers(4, cfg.vocab_size, (B, 1)), jnp.int32)
        run = SERVE_RUN.replace(decode_attn="seq_sharded", mesh=mesh,
                                batch_axes=("data",))
        cache_sh = Sh.cache_shardings(cache, mesh)
        step = jax.jit(lambda p, t, c, n, run=run, cfg=cfg:
                       M.decode_step(cfg, p, t, c, n, run)[:2],
                       out_shardings=(None, cache_sh))
        plain = jax.jit(lambda p, t, c, n, cfg=cfg:
                        M.decode_step(cfg, p, t, c, n, SERVE_RUN)[:2])
        cache_d = jax.device_put(cache, cache_sh)
        for pos in (0, 9, M_ - 1):
            n = jnp.asarray(pos, jnp.int32)
            got, want = step(params, tok, cache_d, n), plain(params, tok,
                                                               cache, n)
            np.testing.assert_allclose(np.asarray(got[0]),
                                       np.asarray(want[0]),
                                       rtol=1e-4, atol=1e-4)
            for name in want[1]:
                np.testing.assert_allclose(np.asarray(got[1][name]),
                                           np.asarray(want[1][name]),
                                           rtol=1e-5, atol=1e-5)
        hlo = step.lower(params, tok, cache_d,
                         jnp.asarray(0, jnp.int32)).compile().as_text()
        shard = min(c.size for c in cache.values()) // mesh.size
        moved = [(op, n) for op, n in _collectives(hlo)
                 if op != "all-reduce"]
        assert all(n < shard for _, n in moved), (moved, shard)
        if cfg.family != "moe":
            assert moved == [], moved
    print("ok seq_sharded_decode_step")


def check_ep_moe_matches_scatter(mesh):
    cfg = dataclasses.replace(
        reduced(get_config("deepseek-v2-lite-16b")),
        n_experts=8, experts_per_tok=2, moe_capacity_factor=64.0)
    key = jax.random.PRNGKey(0)
    p = Moe.moe_init(key, cfg, jnp.float32)
    B, S, d = 2, 16, cfg.d_model
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.float32)

    y_ref, aux_ref = Moe.moe_ffn(cfg, p, x)

    from repro.distributed.moe_parallel import moe_ffn_ep
    # model axis = 4 -> E_local = 2; S=16 % 4 == 0

    def f_ep(p, x):
        y, aux = moe_ffn_ep(cfg, p, x, mesh=mesh, ep_axis="model",
                            batch_axes=("data",))
        return y, aux

    y_ep, aux_ep = jax.jit(f_ep)(p, x)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-4)

    # gradients agree too
    g_ref = jax.grad(lambda p: (Moe.moe_ffn(cfg, p, x)[0] ** 2).sum())(p)
    g_ep = jax.jit(jax.grad(lambda p: (f_ep(p, x)[0] ** 2).sum()))(p)
    for a, b in zip(jax.tree_util.tree_leaves(g_ref),
                    jax.tree_util.tree_leaves(g_ep)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)
    print("ok ep_moe")


def check_param_specs_all_archs(mesh):
    for name in list_configs():
        cfg = get_config(name)
        ps = jax.eval_shape(lambda c=cfg: M.init_model(
            jax.random.PRNGKey(0), c))
        specs = Sh.param_specs(ps, mesh, cfg)
        shardings = jax.tree_util.tree_map(
            lambda s: jax.sharding.NamedSharding(mesh, s), specs)
        # every spec must be consistent with its leaf's shape
        def ok(leaf, sh):
            sh.shard_shape(leaf.shape)  # raises if non-divisible
        jax.tree_util.tree_map(ok, ps, shardings)
    print("ok param_specs_all_archs")


def check_elastic_reshard(tmp, mesh_a, mesh_b):
    from repro.training import checkpoint as CK
    cfg = reduced(get_config("qwen3-1.7b"))
    params = M.init_model(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    sh_a = Sh.param_shardings(params, mesh_a, cfg)
    params_a = jax.tree_util.tree_map(jax.device_put, params, sh_a)
    ck = CK.Checkpointer(tmp)
    ck.save(1, {"params": params_a}, blocking=True)
    # restore onto a DIFFERENT mesh shape
    sh_b = Sh.param_shardings(params, mesh_b, cfg)
    state, _ = ck.restore(shardings={"params": sh_b})
    for a, b in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(state["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("ok elastic_reshard")


if __name__ == "__main__":
    assert len(jax.devices()) == 8, jax.devices()
    mesh = make_mesh((2, 4), ("data", "model"))
    check_sharded_topk(mesh)
    check_seq_sharded_gqa(mesh)
    check_seq_sharded_mla(mesh)
    check_seq_sharded_decode_step()
    check_ep_moe_matches_scatter(mesh)
    check_param_specs_all_archs(mesh)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        mesh_b = make_mesh((4, 2), ("data", "model"))
        check_elastic_reshard(td, mesh, mesh_b)
    print("ALL DISTRIBUTED CHECKS PASSED")
