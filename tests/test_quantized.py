"""Quantized retrieval path: int8 store shards + scales, the quantized
embedding view, the DeviceStore upload-once/delta-append cache, tier
integration, and the facade's ``quantize`` knob (incl. kill/resume
byte-identity of int8 builds)."""
import numpy as np
import pytest

import jax

from repro.core.store import (PrecomputedStore, QuantizedShardedEmbeddings,
                              dequantize_rows, quantize_rows,
                              roundtrip_dtype)
from repro.core.index import (DeviceStore, FlatIndex, IVFIndex,
                              ShardedIndex, auto_index, device_store_for)


def _rows(n, d=48, seed=0, normalize=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    if normalize:
        x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x


# ---------------------------------------------------------------------------
# quantization primitives
# ---------------------------------------------------------------------------


def test_quantize_roundtrip_identity():
    """quant(dequant(quant(x))) == quant(x) bitwise — the property that
    makes tail-shard merges and resumed builds byte-identical."""
    x = _rows(200, normalize=False)
    x[5] = 0.0                       # zero row edge: scale falls back to 1
    q1, s1 = quantize_rows(x)
    q2, s2 = quantize_rows(dequantize_rows(q1, s1))
    assert np.array_equal(q1, q2)
    assert np.array_equal(s1, s2)
    assert q1.dtype == np.int8 and s1.dtype == np.float32
    assert np.abs(q1).max() <= 127
    # error bound: half a quantization step per element
    err = np.abs(dequantize_rows(q1, s1) - x)
    assert np.all(err <= s1[:, None] * 0.5 + 1e-9)


def test_roundtrip_dtype_matches_legacy_float_path():
    x = _rows(64, normalize=False)
    assert np.array_equal(roundtrip_dtype(x, "float16"),
                          x.astype(np.float16).astype(np.float32))
    assert roundtrip_dtype(x, "float32") is not None
    np.testing.assert_array_equal(roundtrip_dtype(x, "float32"), x)
    np.testing.assert_array_equal(
        roundtrip_dtype(x, "int8"), dequantize_rows(*quantize_rows(x)))


# ---------------------------------------------------------------------------
# int8 store format
# ---------------------------------------------------------------------------


def test_int8_store_roundtrip(tmp_path):
    import json
    x = _rows(200)
    st = PrecomputedStore(tmp_path / "s", dim=48, emb_dtype="int8",
                          shard_rows=64)
    for lo in range(0, 200, 37):         # odd batching + mid-build flushes
        hi = min(lo + 37, 200)
        st.add_batch(x[lo:hi], [f"q{i}" for i in range(lo, hi)],
                     [f"r{i}" for i in range(lo, hi)])
        if lo % 2:
            st.flush()
    st.close()

    man = json.loads((tmp_path / "s" / "manifest.json").read_text())
    assert man["emb_dtype"] == "int8"
    assert all("scale_file" in s for s in man["shards"])
    for s in man["shards"]:              # scales on disk, row-aligned
        assert (tmp_path / "s" / s["scale_file"]).exists()
        assert np.load(tmp_path / "s" / s["scale_file"]).shape == \
            (s["rows"],)

    st2 = PrecomputedStore.open_(tmp_path / "s")
    assert st2.quantized
    e = st2.embeddings()
    assert isinstance(e, QuantizedShardedEmbeddings)
    assert e.is_quantized and e.dtype == np.float32
    assert e.shape == (200, 48)
    deq = np.asarray(e)
    _, sc = quantize_rows(x)
    assert np.all(np.abs(deq - x) <= sc[:, None] * 0.5 + 1e-9)
    # view accessors: dequantized on the float surface, raw underneath
    np.testing.assert_array_equal(e[3], deq[3])
    np.testing.assert_array_equal(e[10:20], deq[10:20])
    qv, qs = e.take_q([0, 63, 64, 199])
    assert qv.dtype == np.int8 and qs.dtype == np.float32
    np.testing.assert_array_equal(dequantize_rows(qv, qs),
                                  deq[[0, 63, 64, 199]])
    assert sum(p.shape[0] for p in e.iter_shards()) == 200
    assert all(v.dtype == np.int8 for v, _ in e.iter_qshards())
    # content is the direct per-row quantization of the source rows,
    # independent of add/flush batching
    qv_all, qs_all = st2.embeddings().take_q(np.arange(200))
    qd, sd = quantize_rows(x)
    assert np.array_equal(qv_all, qd) and np.array_equal(qs_all, sd)
    # mmap=False materializes dequantized f32
    np.testing.assert_array_equal(st2.embeddings(mmap=False), deq)
    st2.close()


def test_int8_store_bytes_under_30pct_of_fp32(tmp_path):
    x = _rows(512)
    for dtype in ("int8", "float32"):
        st = PrecomputedStore(tmp_path / dtype, dim=48, emb_dtype=dtype)
        st.add_batch(x, ["q"] * 512, ["r"] * 512)
        st.close()
    b8 = PrecomputedStore.open_(tmp_path / "int8").storage_bytes()
    b32 = PrecomputedStore.open_(tmp_path / "float32").storage_bytes()
    assert b8["index_bytes"] / b32["index_bytes"] <= 0.30
    assert b8["rows"] == b32["rows"] == 512


def test_int8_store_pending_rows_visible(tmp_path):
    """Unflushed rows appear in the quantized view exactly like flushed
    ones (the §3.1 write-back window before the periodic flush)."""
    x = _rows(30)
    st = PrecomputedStore(tmp_path / "s", dim=48, emb_dtype="int8")
    st.add_batch(x[:20], ["q"] * 20, ["r"] * 20)
    st.flush()
    st.add_batch(x[20:], ["q"] * 10, ["r"] * 10)   # pending, no flush
    e = st.embeddings()
    assert e.shape == (30, 48)
    qv, qs = e.take_q(np.arange(30))
    qd, sd = quantize_rows(x)
    assert np.array_equal(qv, qd) and np.array_equal(qs, sd)
    st.close()


# ---------------------------------------------------------------------------
# DeviceStore: upload once, append deltas, scan exactly
# ---------------------------------------------------------------------------


def _int8_store(tmp_path, x, name="s", shard_rows=256):
    st = PrecomputedStore(tmp_path / name, dim=x.shape[1],
                          emb_dtype="int8", shard_rows=shard_rows)
    st.add_batch(x, [f"q{i}" for i in range(len(x))], ["r"] * len(x))
    st.flush()
    return st


def test_device_store_cache_and_delta_append(tmp_path):
    x = _rows(600)
    st = _int8_store(tmp_path, x)
    idx = auto_index(st)
    assert isinstance(idx, FlatIndex)
    dev = idx.dev
    u0 = dev.uploads
    assert dev.n_rows == 600 and dev.quantized
    # rebuild over the same store: cached residency, zero new uploads
    idx2 = auto_index(st)
    assert idx2.dev is dev and dev.uploads == u0
    # store grows (write-back): only the delta ships
    st.add_batch(x[:50], ["nq"] * 50, ["nr"] * 50)
    st.flush()
    idx3 = auto_index(st)
    assert idx3.dev is dev
    assert dev.n_rows == 650 and dev.uploads == u0 + 1
    # shrinking is refused (a different store at the same identity)
    with pytest.raises(ValueError):
        dev.sync(_rows(10))
    st.close()


def test_device_store_search_matches_exact_fp32_of_dequantized(tmp_path):
    """The gemm-layout scan is EXACT over the dequantized rows — the only
    error vs raw fp32 is the quantization itself."""
    x = _rows(500)
    st = _int8_store(tmp_path, x)
    q = _rows(16, seed=5)
    v, i = DeviceStore(st).search(q, 5)
    deq = np.asarray(st.embeddings())
    s = q @ deq.T
    np.testing.assert_allclose(
        v, np.sort(s, axis=1)[:, ::-1][:, :5], rtol=1e-5, atol=1e-6)
    st.close()


def test_device_store_kernel_layout_agrees_with_gemm(tmp_path):
    x = _rows(700)
    st = _int8_store(tmp_path, x)
    q = x[np.random.default_rng(7).integers(0, 700, 32)]
    vg, ig = DeviceStore(st, layout="gemm").search(q, 3)
    vk, ik = DeviceStore(st, layout="kernel").search(q, 3)
    # kernel layout quantizes the QUERY block too; scores agree within
    # the query's own rounding and top-1 identity on serving queries
    np.testing.assert_allclose(vk, vg, atol=5e-3)
    assert (ik[:, 0] == ig[:, 0]).mean() >= 0.99
    st.close()


def test_device_store_fp16_ships_native_and_casts_once(tmp_path):
    """fp16 stores: the resident operand is built once at construction —
    searches run on it directly with no per-batch upcast of the matrix."""
    x = _rows(300)
    st = PrecomputedStore(tmp_path / "s", dim=48, emb_dtype="float16")
    st.add_batch(x, ["q"] * 300, ["r"] * 300)
    st.flush()
    idx = auto_index(st)
    dev = idx.dev
    u0 = dev.uploads
    q = _rows(8, seed=9)
    v, i = idx.search(q, 4)
    v2, i2 = idx.search(q, 4)
    assert dev.uploads == u0          # searching never re-ships anything
    ref = q @ np.asarray(st.embeddings(), np.float32).T
    np.testing.assert_allclose(
        v, np.sort(ref, axis=1)[:, ::-1][:, :4], rtol=1e-3, atol=1e-4)
    # kernel layout ships fp16 and upcasts ONCE at upload (v5e cannot
    # load f16 vectors); searches then run on the resident f32 operand
    devk = DeviceStore(st, layout="kernel")
    import jax.numpy as jnp
    assert devk._x.dtype == jnp.float32
    vk, ik = devk.search(q, 4)
    np.testing.assert_allclose(vk, v, rtol=1e-3, atol=1e-4)
    np.testing.assert_array_equal(ik, i)
    st.close()


@pytest.mark.parametrize("n,d,k", [(700, 384, 1), (1300, 48, 8)])
def test_device_store_fp16_kernel_layout_scores_like_ref(tmp_path, n, d, k):
    """The kernel layout of a float16 store (f32 residency, Pallas float
    kernel in interpret mode) returns ``ref.mips_topk_ref``'s rows and
    scores over the same fp16-rounded rows."""
    import jax.numpy as jnp

    from repro.kernels import ref
    x = _rows(n, d=d, seed=n)
    st = PrecomputedStore(tmp_path / "s", dim=d, emb_dtype="float16")
    st.add_batch(x, ["q"] * n, ["r"] * n)
    st.flush()
    q = _rows(32, d=d, seed=n + 1)
    v, i = DeviceStore(st, layout="kernel").search(q, k)
    x16 = np.asarray(st.embeddings()).astype(np.float32)
    vr, ir = ref.mips_topk_ref(jnp.asarray(q), jnp.asarray(x16), k)
    np.testing.assert_array_equal(i, np.asarray(ir))
    # same f32 products, summed in another order (one dot per tile vs one
    # matmul): unit-norm scores agree to a few f32 ulps
    np.testing.assert_allclose(v, np.asarray(vr), rtol=0, atol=1e-6)
    st.close()


def test_ivf_tier_does_not_pin_flat_residency(tmp_path):
    """auto_index at the IVF tier must not create (and permanently cache)
    a full flat device copy just to seed k-means; a residency left over
    from the flat tier IS reused."""
    from repro.core.index import _DEVICE_STORES, cached_device_store
    x = _rows(600, d=32)
    st = _int8_store(tmp_path, x)
    assert cached_device_store(st) is None
    idx = auto_index(st, flat_max_rows=100)       # forces the IVF tier
    assert isinstance(idx, IVFIndex)
    assert cached_device_store(st) is None        # no residency created
    # a flat-tier store that later crosses the boundary reuses its cache
    dev = device_store_for(st)
    assert cached_device_store(st) is dev
    idx2 = auto_index(st, flat_max_rows=100)
    assert isinstance(idx2, IVFIndex)
    assert _DEVICE_STORES.get(st) is dev
    st.close()


def test_device_store_for_keys_on_store_identity(tmp_path):
    x = _rows(100)
    st = _int8_store(tmp_path, x)
    a = device_store_for(st)
    b = device_store_for(st)
    assert a is b
    # raw arrays have no stable identity: fresh instance each time
    assert device_store_for(x) is not device_store_for(x)
    st.close()


# ---------------------------------------------------------------------------
# tiers over quantized views
# ---------------------------------------------------------------------------


def test_int8_flat_recall_parity_vs_fp32(tmp_path):
    x = _rows(1500, d=64)
    rng = np.random.default_rng(3)
    q = x[rng.integers(0, 1500, 64)] \
        + 0.05 * rng.normal(size=(64, 64)).astype(np.float32)
    q = (q / np.linalg.norm(q, axis=1, keepdims=True)).astype(np.float32)
    _, i32 = FlatIndex(x).search(q, 1)
    st = _int8_store(tmp_path, x)
    _, i8 = auto_index(st).search(q, 1)
    assert (i8[:, 0] == i32[:, 0]).mean() >= 0.99
    st.close()


def test_ivf_accepts_quantized_view(tmp_path):
    x = _rows(1200, d=64)
    st = _int8_store(tmp_path, x)
    ivf = IVFIndex(st.embeddings(), n_lists=16, nprobe=8)
    assert ivf.centroids.dtype == np.float32     # coarse probe stays fp32
    rng = np.random.default_rng(4)
    q = x[rng.integers(0, 1200, 32)]
    v, i = ivf.search(q, 5)
    assert v.shape == (32, 5)
    # exact duplicates of stored rows must come back as themselves
    assert (v[:, 0] > 0.98).mean() > 0.9
    st.close()


def test_ivf_save_load_roundtrip_on_quantized_store(tmp_path):
    x = _rows(900, d=64)
    st = _int8_store(tmp_path, x)
    ivf = IVFIndex(st.embeddings(), n_lists=12, nprobe=6)
    ivf.save(tmp_path / "ivf.npz")
    loaded = IVFIndex.load(tmp_path / "ivf.npz", st.embeddings())
    q = _rows(8, d=64, seed=2)
    v1, i1 = ivf.search(q, 3)
    v2, i2 = loaded.search(q, 3)
    np.testing.assert_allclose(v1, v2, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(i1, i2)
    st.close()


def test_sharded_index_int8_matches_flat(tmp_path):
    from jax.sharding import Mesh
    x = _rows(513, d=64)                  # odd: forces padded rows + mask
    st = _int8_store(tmp_path, x)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("model",))
    sh = ShardedIndex(st.embeddings(), mesh)
    assert sh.scales is not None and len(sh) == 513
    q = _rows(8, d=64, seed=6)
    vs, is_ = sh.search(q, 5)
    # both tiers quantize the query and dequantize in the same order, so
    # the sharded scan returns the flat kernel layout's exact scores
    vf, if_ = DeviceStore(st, layout="kernel").search(q, 5)
    np.testing.assert_array_equal(vs, vf)
    np.testing.assert_array_equal(is_, if_)
    st.close()


# ---------------------------------------------------------------------------
# facade integration
# ---------------------------------------------------------------------------


def test_facade_quantize_knob_end_to_end(tmp_path):
    from repro.api import StorInfer, SystemCfg
    from repro.core.kb import build_kb
    kb = build_kb("squad", n_docs=6)
    cfg = SystemCfg(quantize=True, s_th_run=0.9)
    assert cfg.emb_dtype == "int8"
    # emb_dtype spelling implies the knob too
    assert SystemCfg(emb_dtype="int8").quantize
    with StorInfer.build(kb, cfg, tmp_path / "sys", n_pairs=200) as si:
        assert str(si.store.emb_dtype) == "int8"
        q0 = si.store.get_pair(0)[0]
        r = si.query(q0)
        assert r.hit and r.score >= 0.99
        rs = si.query_batch([q0, "completely novel zebra question"])
        assert rs[0].hit and not rs[1].hit
        with si.serve():
            assert si.submit(q0).result(timeout=30).hit
        sb = si.stats().store_bytes
        assert sb["index_bytes"] < 200 * 384 * 1.5   # int8-ish, not fp32
    # reopen honors the manifest dtype regardless of cfg
    with StorInfer.open(tmp_path / "sys", SystemCfg(s_th_run=0.9)) as si2:
        assert si2.store.quantized
        assert si2.query(q0).hit


def test_facade_rebuild_reuses_device_residency(tmp_path):
    from repro.api import StorInfer, SystemCfg
    from repro.core.kb import build_kb
    kb = build_kb("squad", n_docs=6)
    cfg = SystemCfg(quantize=True, s_th_run=0.9)
    with StorInfer.build(kb, cfg, tmp_path / "sys", n_pairs=150) as si:
        dev = si.index.dev
        n0, u0 = dev.n_rows, dev.uploads
        e = si.embedder.encode(["fresh writeback query"])
        si.store.add_batch(e, ["fresh writeback query"], ["resp."])
        si._batched.flush_and_rebuild()
        assert si._batched.index.dev is dev      # cached, not re-uploaded
        assert dev.n_rows == n0 + 1 and dev.uploads == u0 + 1
        v, i = si._batched.index.search(e, 1)
        assert int(i[0, 0]) == n0 and v[0, 0] > 0.99


def test_int8_build_kill_resume_byte_identical(tmp_path):
    """The precompute pipeline's resume byte-identity holds for quantized
    stores (per-row quantization + the store-dtype dedup round-trip)."""
    from repro.api import StorInfer, SystemCfg
    from repro.core.kb import build_kb
    from repro.core.precompute import BuildKilled, PrecomputeCfg
    kb = build_kb("squad", n_docs=5)
    cfg = SystemCfg(quantize=True, index="none",
                    precompute=PrecomputeCfg(wave=8, checkpoint_every=2))
    with StorInfer.build(kb, cfg, tmp_path / "full", n_pairs=120) as full:
        assert full.store.count == 120
    with pytest.raises(BuildKilled):
        StorInfer.build(kb, cfg, tmp_path / "killed", n_pairs=120,
                        _kill_after_waves=4)
    with StorInfer.build(kb, cfg, tmp_path / "killed",
                         n_pairs=120) as resumed:
        assert resumed.store.count == 120
    for name in sorted(p.name for p in (tmp_path / "full").glob("emb_*")) \
            + ["text.jsonl", "offsets.npy"]:
        a = (tmp_path / "full" / name).read_bytes()
        b = (tmp_path / "killed" / name).read_bytes()
        assert a == b, f"{name} differs between full and resumed build"
