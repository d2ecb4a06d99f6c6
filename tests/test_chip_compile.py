"""Compiles the serving path's device programs for a described TPU v5e
(``v5e:2x2``) at real widths, with no chip attached: the MIPS kernels at
the paper's 150K-row store, qwen3-1.7b's prefill and decode chunk at full
width, and the sharded int8 scan over four chips. The TPU compiler
refuses here what interpret mode accepts (unsupported Mosaic ops, loads of
dtypes the chip cannot load, programs that do not fit HBM).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and every pytest worker
imports this file."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

N, D = 150_016, 384           # the smoke store: 150K rows, 293 tiles of 512
HBM_BYTES = 16e9              # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("Q,k", [(1, 1), (1, 8), (32, 1), (32, 8)])
def test_int8_mips_kernel_compiles(one_chip, Q, k):
    from repro.kernels.mips_topk_int8 import mips_topk_int8_pallas
    f = jax.jit(lambda q, qs, x, xs: mips_topk_int8_pallas(
        q, qs, x, xs, k, interpret=False))
    c = f.lower(_sds((Q, D), jnp.int8, one_chip),
                _sds((Q,), jnp.float32, one_chip),
                _sds((N, D), jnp.int8, one_chip),
                _sds((N,), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("Q,k", [(1, 1), (1, 8), (32, 1), (32, 8)])
def test_float_mips_kernel_compiles_at_f32_residency(one_chip, Q, k):
    from repro.kernels.mips_topk import mips_topk_pallas
    f = jax.jit(lambda q, x: mips_topk_pallas(q, x, k, interpret=False))
    c = f.lower(_sds((Q, D), jnp.float32, one_chip),
                _sds((N, D), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture(scope="module")
def engine_shapes(one_chip):
    """qwen3-1.7b at full width as the facade builds it (params at the
    configuration's dtype, the engine's run, EngineCfg's max_len/chunk,
    the pipeline's 4 decode slots)."""
    from repro.api import EngineCfg
    from repro.configs import get_config
    from repro.core.tokenizer import Tokenizer
    from repro.models import model as M
    from repro.serving.engine import Engine
    ecfg, slots = EngineCfg(), 4
    cfg = get_config(ecfg.arch)
    eng = Engine(cfg, None, Tokenizer(["x"]), max_len=ecfg.max_len,
                 chunk=ecfg.chunk)
    place = lambda s: _sds(s.shape, s.dtype, one_chip)    # noqa: E731
    params = jax.tree_util.tree_map(place, jax.eval_shape(
        lambda: M.init_model(jax.random.PRNGKey(0), cfg)))
    cache = jax.tree_util.tree_map(
        place, M.cache_struct(cfg, slots, ecfg.max_len))
    return eng, params, cache, slots


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = m.argument_size_in_bytes + m.temp_size_in_bytes
    assert used < HBM_BYTES, used


def _decode_chunk(eng, params, cache, B, one_chip):
    return eng._decode_chunk.lower(
        params, _sds((B, 1), jnp.int32, one_chip), cache,
        _sds((), jnp.int32, one_chip), _sds((2,), jnp.uint32, one_chip),
        None, _sds((B,), jnp.bool_, one_chip)).compile()


def test_engine_decode_chunk_compiles_full_width(engine_shapes, one_chip):
    eng, params, cache, B = engine_shapes
    _fits_one_chip(_decode_chunk(eng, params, cache, B, one_chip))


def test_engine_decode_chunk_takes_bf16_weights_at_16_slots(engine_shapes,
                                                             one_chip):
    """The benchmark's 16 slots: bf16 weights (3.44 GB) and the cache,
    where f32 weights alone are 6.9 GB."""
    from repro.models import model as M
    eng, params, _, _ = engine_shapes
    B = 16
    cache = jax.tree_util.tree_map(
        lambda s: _sds(s.shape, s.dtype, one_chip),
        M.cache_struct(eng.cfg, B, eng.max_len))
    c = _decode_chunk(eng, params, cache, B, one_chip)
    assert c.memory_analysis().argument_size_in_bytes < 4.5e9


def test_engine_prefill_compiles_full_width(engine_shapes, one_chip):
    eng, params, _, _ = engine_shapes
    c = eng._prefill.lower(params, _sds((1, 24), jnp.int32,
                                        one_chip)).compile()
    _fits_one_chip(c)


def test_sharded_int8_scan_compiles_on_four_chips(topo):
    from repro.distributed.topk import sharded_mips_topk
    mesh = jax.sharding.Mesh(np.asarray(topo.devices).reshape(1, 4),
                             ("data", "model"))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("model"))
    f = jax.jit(lambda q, qs, x, xs: sharded_mips_topk(
        q, x, 1, mesh=mesh, scales=xs, n_real=N, q_scale=qs))
    c = f.lower(_sds((32, D), jnp.int8, rep), _sds((32,), jnp.float32, rep),
                _sds((N, D), jnp.int8, NamedSharding(mesh,
                                                     P("model", None))),
                _sds((N,), jnp.float32, rows)).compile()
    assert "all-gather" in c.as_text()
    # each chip holds a quarter of the rows
    assert c.memory_analysis().argument_size_in_bytes < N * (D + 4) / 3
