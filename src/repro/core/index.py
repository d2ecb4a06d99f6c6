"""MIPS indexes over the precomputed-query embeddings.

TPU adaptation of the paper's DiskANN: graph-ANN pointer-chasing is
hostile to the MXU/HBM burst model, so the index is a batched tiled MIPS
scan — a matmul, the single most roofline-friendly op on the platform —
with IVF coarse pruning for sub-linear probes and a mesh-sharded variant
(rows over "model", distributed top-k) for pod-scale stores.

  FlatIndex        — exact brute MIPS (jnp matmul + top_k; the Pallas
                     ``mips_topk`` kernel implements the same contract on
                     TPU).
  IVFIndex         — k-means coarse quantizer, scans nprobe lists; persists
                     its centroids + padded list layout (``save``/``load``)
                     so reopening a paper-scale store skips k-means.
  ShardedIndex     — rows sharded over a mesh axis, local top-k + all-gather
                     combine (repro.distributed.topk).
  IncrementalIndex — append-only max-similarity index for the OFFLINE dedup
                     loop: ``add()`` + ``max_sim()``, flat below the tier
                     boundary, IVF with assign-to-nearest-centroid appends
                     and amortized re-clustering above it.

``auto_index`` picks between the serving tiers from store size and mesh
availability (see ``select_tier`` for the exact boundaries) so callers —
the batched runtime in particular — never hard-code a tier; pass
``cache_dir=`` to load/save the IVF build product instead of re-running
k-means on every reopen.
"""
from __future__ import annotations

import functools
import json
import os
import threading
import weakref
import zlib
from pathlib import Path
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.store import quantize_rows

# Below this row count an exact flat scan is one small matmul and beats any
# pruning overhead; above it IVF's nprobe/n_lists scan fraction wins. The
# paper's 150K-pair store lands in the IVF tier.
FLAT_MAX_ROWS = 32768
# Sharding only pays once each shard is a non-trivial scan.
SHARD_MIN_ROWS = 4 * FLAT_MAX_ROWS


def _device_embs(embs) -> jnp.ndarray:
    """Host→device (N, D) float32 without a full host-side copy: a
    ``ShardedEmbeddings`` view moves one shard at a time — shipped in its
    STORED dtype (fp16 halves the transfer, int8 quarters it) and upcast /
    dequantized once on the device — so peak host memory is one shard and
    the link never carries an inflated fp32 copy."""
    if hasattr(embs, "iter_qshards"):
        parts = [jnp.asarray(np.asarray(v)).astype(jnp.float32)
                 * jnp.asarray(np.asarray(s))[:, None]
                 for v, s in embs.iter_qshards()]
    elif hasattr(embs, "iter_shards"):
        parts = [jnp.asarray(np.asarray(s)).astype(jnp.float32)
                 for s in embs.iter_shards()]
    else:
        return jnp.asarray(np.asarray(embs)).astype(jnp.float32)
    if not parts:
        return jnp.zeros(embs.shape, jnp.float32)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, 0)


# ---------------------------------------------------------------------------
# Device-resident store cache (the serving hot path's upload-once layer)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnums=(2,))
def _flat_scan_T(q, xT, k):
    """The GEMM-layout flat scan: q (Q, D) @ xT (D, N) + top-k, one fused
    dispatch over the device-resident operand."""
    return jax.lax.top_k(q @ xT, k)


# Profiler span (``jax.profiler.TraceAnnotation``; inert unless a profiler
# runs) from a flat scan's call to its results on the host: the scan
# program's queue on the device, its run, and the read back.
SPAN_SCAN = "storinfer.search.scan"

# rows gathered to the host per DeviceStore.sync step (bounds peak host
# memory during the initial upload of a paper-scale store)
_SYNC_ROWS = 65536


class DeviceStore:
    """Device-resident copy of a store's embeddings: upload once, append
    deltas, scan without ever re-shipping N×D.

    Pre-PR, every index (re)build round-tripped the full matrix through
    host fp32 (and §3.1 write-back rebuilds re-uploaded everything); this
    cache is keyed per store (``device_store_for``) and survives tier
    rebuilds, so a rebuild after write-backs ships only the new rows.

    Residency layout per backend (``layout=``):

    * ``"kernel"`` (default on TPU) — quantized stores stay int8 values +
      per-row f32 scales (feeding the fused ``mips_topk_int8`` Pallas
      kernel; hot-path HBM bytes drop 4x vs fp32). Float stores are
      shipped in their stored dtype and upcast to f32 once on the device
      (``mips_topk``): v5e cannot load f16 vectors, and f32 keeps the
      scores exact against an f32 reference.
    * ``"gemm"`` (default on CPU) — no int8 MXU exists and XLA's CPU int8
      GEMM is several times SLOWER than Eigen's fp32, so shards are
      dequantized/upcast ONCE at upload into the transposed (D, N) fp32
      layout the CPU GEMM wants (measured ~2x over the old per-(N,D)
      resident scan at N=100K, Q<=32). Disk/transfer savings and the
      quantization error are identical to the kernel layout; the
      RAM-for-speed trade is explicit.

    ``search`` is exact over whatever representation is resident. On a
    quantized store the kernel layout also quantizes the QUERY block
    (int8 x int8 -> int32 on the MXU), so its scores differ from the
    gemm layout's (f32 query x dequantized store) by the query's own
    rounding — bounded by ~query_scale * sqrt(D)/127, ~2e-3 for
    normalized 384-d embeddings; top-1 agreement on serving workloads is
    >= 0.99 either way (tests pin both).
    """

    def __init__(self, source, layout: str = "auto"):
        if layout == "auto":
            layout = "kernel" if jax.default_backend() == "tpu" else "gemm"
        if layout not in ("kernel", "gemm"):
            raise ValueError(f"unknown DeviceStore layout {layout!r}")
        self.layout = layout
        self.n_rows = 0
        self.dim: Optional[int] = None
        self.quantized = False
        self._xT = None        # gemm: (D, N) f32
        self._x = None         # kernel: (N, D) int8, or f32 for floats
        self._scales = None    # kernel + quantized: (N,) f32
        self.uploads = 0       # host→device transfers (tests/benchmarks)
        # background §3.1 rebuilds sync() deltas while the serving path
        # searches the SAME cached residency — the lock keeps the
        # (_x, _scales, n_rows) triple consistent across that race
        self._sync_lock = threading.Lock()
        self.sync(source)

    @staticmethod
    def _view(source):
        return source.embeddings() if hasattr(source, "embeddings") \
            else source

    def sync(self, source) -> "DeviceStore":
        """Ingest rows the device copy doesn't have yet (the §3.1
        write-back delta); a no-op when the store hasn't grown. Safe to
        call from a background rebuild while searches are in flight."""
        with self._sync_lock:
            return self._sync_locked(source)

    def _sync_locked(self, source) -> "DeviceStore":
        view = self._view(source)
        n, d = int(view.shape[0]), int(view.shape[1])
        if self.dim is None:
            self.dim = d
        elif d != self.dim:
            raise ValueError(f"dim changed {self.dim} -> {d}")
        if n < self.n_rows:
            raise ValueError(
                f"store shrank ({self.n_rows} -> {n} rows): DeviceStore "
                "deltas are append-only — build a fresh one")
        if n == self.n_rows:
            return self
        quantized = bool(getattr(view, "is_quantized", False))
        if self.n_rows == 0:
            self.quantized = quantized
        elif quantized != self.quantized:
            raise ValueError("store changed quantization mid-flight")

        def gather(rows):
            # view.take gathers ROWS on shard views; ndarray.take would
            # gather flat elements, so plain arrays index instead
            return view.take(rows) if hasattr(view, "iter_shards") \
                else np.asarray(view[rows])

        # chunked so peak host memory is one chunk, not the whole delta
        chunks = [np.arange(lo, min(lo + _SYNC_ROWS, n))
                  for lo in range(self.n_rows, n, _SYNC_ROWS)]
        if self.layout == "gemm":
            # dequant/upcast + transpose on the host per chunk: the scan
            # operand must be PHYSICALLY (D, N) — transposing on device
            # would fold back into the slow (N, D)-contraction dot
            parts = [jnp.asarray(
                np.ascontiguousarray(gather(c).astype(np.float32).T))
                for c in chunks]
            parts = ([] if self._xT is None else [self._xT]) + parts
            self._xT = parts[0] if len(parts) == 1 \
                else jnp.concatenate(parts, axis=1)
        elif self.quantized:
            got = [view.take_q(c) for c in chunks]
            xs = ([] if self._x is None else [self._x]) \
                + [jnp.asarray(v) for v, _ in got]
            ss = ([] if self._scales is None else [self._scales]) \
                + [jnp.asarray(s) for _, s in got]
            self._x = xs[0] if len(xs) == 1 else jnp.concatenate(xs, 0)
            self._scales = ss[0] if len(ss) == 1 \
                else jnp.concatenate(ss, 0)
        else:
            xs = ([] if self._x is None else [self._x]) \
                + [jnp.asarray(gather(c)).astype(jnp.float32)
                   for c in chunks]
            self._x = xs[0] if len(xs) == 1 else jnp.concatenate(xs, 0)
        self.uploads += len(chunks)
        self.n_rows = n
        return self

    def matrix(self) -> jnp.ndarray:
        """The resident rows as a device (N, D) f32 matrix (IVF fits /
        list builds reuse the residency instead of re-uploading)."""
        with self._sync_lock:
            n_rows, xT, x, scales = (self.n_rows, self._xT, self._x,
                                     self._scales)
        if n_rows == 0:
            return jnp.zeros((0, self.dim or 0), jnp.float32)
        if self.layout == "gemm":
            return xT.T
        x = x.astype(jnp.float32)
        return x * scales[:, None] if self.quantized else x

    def search(self, queries, k: int):
        """Exact flat MIPS over the resident rows: (vals, idx) ndarrays."""
        q = np.asarray(queries, np.float32)
        k = int(k)
        with self._sync_lock:      # consistent (operand, scales, n) triple
            n_rows = self.n_rows
            xT, x, scales = self._xT, self._x, self._scales
        if k > n_rows:
            raise ValueError(f"k={k} exceeds store rows N={n_rows}")
        with jax.profiler.TraceAnnotation(SPAN_SCAN):
            if self.layout == "gemm":
                v, i = _flat_scan_T(jnp.asarray(q), xT, k)
            elif self.quantized:
                from repro.kernels.ops import mips_topk_int8
                q8, qs = quantize_rows(q)
                v, i = mips_topk_int8(jnp.asarray(q8), jnp.asarray(qs),
                                      x, scales, k)
            else:
                from repro.kernels.ops import mips_topk
                v, i = mips_topk(jnp.asarray(q), x, k)
            return np.asarray(v), np.asarray(i)


# One DeviceStore per live store object: index rebuilds (write-backs, tier
# changes) get the cached residency + a delta sync instead of a re-upload.
_DEVICE_STORES: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def cached_device_store(store) -> Optional[DeviceStore]:
    """The store's cached ``DeviceStore`` if one already exists, delta-
    synced — or None, WITHOUT creating residency. IVF refits use this:
    a store that grew out of the flat tier reuses the flat residency it
    already paid for, but an IVF-scale store never pins a full flat
    device copy just to seed k-means."""
    try:
        ds = _DEVICE_STORES.get(store)
    except TypeError:
        return None
    return ds.sync(store) if ds is not None else None


def device_store_for(store, layout: str = "auto") -> DeviceStore:
    """The per-store cached ``DeviceStore`` (created on first use, delta-
    synced on every later call). Non-store sources (raw arrays, bare
    views) get a fresh uncached instance — there is no stable identity to
    key on. A cached entry is only reused when its layout matches."""
    if layout == "auto":
        layout = "kernel" if jax.default_backend() == "tpu" else "gemm"
    if not hasattr(store, "embeddings"):
        return DeviceStore(store, layout=layout)
    try:
        cached = _DEVICE_STORES.get(store)
    except TypeError:
        cached = None
    if cached is not None and cached.layout == layout:
        return cached.sync(store)
    ds = DeviceStore(store, layout=layout)
    try:
        _DEVICE_STORES[store] = ds
    except TypeError:
        pass
    return ds


class FlatIndex:
    """Exact MIPS over a device-resident copy of the embeddings
    (``DeviceStore``): the operand is shipped once in its stored dtype and
    cast/dequantized once at upload — never per query batch — and index
    rebuilds over the same store reuse the residency via
    ``device_store_for``. ``use_kernel`` forces the Pallas kernel layout
    (interpret mode on CPU); the default picks per backend."""

    def __init__(self, embs: np.ndarray = None, use_kernel: bool = False,
                 device: Optional[DeviceStore] = None):
        if device is None:
            device = DeviceStore(embs,
                                 layout="kernel" if use_kernel else "auto")
        self.dev = device
        self.use_kernel = use_kernel or device.layout == "kernel"

    def search(self, queries: np.ndarray, k: int):
        return self.dev.search(queries, k)

    def __len__(self):
        return self.dev.n_rows


# ---------------------------------------------------------------------------
# IVF (k-means coarse quantizer)
# ---------------------------------------------------------------------------


def kmeans(x: jnp.ndarray, n_clusters: int, iters: int = 10, seed: int = 0):
    """Plain Lloyd's on the device. Returns (centroids, assignment).

    ``n_clusters`` is clamped to the row count — sampling n_clusters
    distinct seed rows with ``replace=False`` is otherwise impossible (and
    used to crash on stores smaller than the requested list count)."""
    n = x.shape[0]
    n_clusters = max(1, min(int(n_clusters), int(n)))
    key = jax.random.PRNGKey(seed)
    init = jax.random.choice(key, n, (n_clusters,), replace=False)
    cent = x[init]

    # x is a traced ARGUMENT, not a closure capture: captured arrays are
    # baked into the jaxpr as constants, which XLA then constant-folds
    # (minutes of compile at paper-scale row counts, once per refit)
    def step(x, cent):
        d = (jnp.sum(x * x, 1)[:, None] - 2 * x @ cent.T
             + jnp.sum(cent * cent, 1)[None, :])
        a = jnp.argmin(d, axis=1)
        oh = jax.nn.one_hot(a, cent.shape[0], dtype=x.dtype)
        sums = oh.T @ x
        counts = oh.sum(0)[:, None]
        new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1), cent)
        return new, a

    step = jax.jit(step)
    for _ in range(iters):
        cent, assign = step(x, cent)
    return cent, assign


class IVFIndex:
    """IVF-Flat: coarse k-means, probe top-``nprobe`` lists, exact scan.

    Padded list layout (lists, cap, dim) so the probe scan is one gather +
    batched matmul — TPU-friendly, no ragged pointers.

    ``save``/``load`` persist the k-means product (centroids + the padded
    id layout; the vectors themselves are re-gathered from the store on
    load), so reopening a 150K-row store costs one gather instead of a
    fresh k-means fit.
    """

    def __init__(self, embs: np.ndarray, n_lists: int = 64, nprobe: int = 8,
                 seed: int = 0, device: Optional[DeviceStore] = None):
        # an ALREADY-cached DeviceStore (auto_index passes one when the
        # store grew out of the flat tier) seeds the fit from the resident
        # rows instead of re-uploading N×D; otherwise the fit matrix is a
        # transient local, released after __init__ — an IVF-scale store
        # must not pin a flat device copy. Quantized views are accepted
        # either way; centroids, fit, and padded probe lists stay fp32
        # (coarse probing is too precision-sensitive to quantize).
        x = device.matrix() if device is not None else _device_embs(embs)
        self.n_total = int(x.shape[0])
        # clamp: k-means cannot seed more lists than there are rows
        self.n_lists = max(1, min(n_lists, self.n_total))
        self.nprobe = min(nprobe, self.n_lists)
        self.loaded_from: Optional[str] = None
        cent, assign = kmeans(x, self.n_lists, seed=seed)
        self.centroids = cent
        assign = np.asarray(assign)
        cap = max(int(np.max(np.bincount(assign, minlength=self.n_lists))),
                  1)
        D = x.shape[1]
        buf = np.zeros((self.n_lists, cap, D), np.float32)
        ids = np.full((self.n_lists, cap), -1, np.int32)
        fill = np.zeros(self.n_lists, np.int32)
        xe = np.asarray(x)
        for row, a in enumerate(assign):
            buf[a, fill[a]] = xe[row]
            ids[a, fill[a]] = row
            fill[a] += 1
        self.lists = jnp.asarray(buf)
        self.ids = jnp.asarray(ids)
        self._search = jax.jit(self._search_impl, static_argnums=(1,))

    # -- persistence ----------------------------------------------------------
    @staticmethod
    def _fingerprint(lists: np.ndarray, ids: np.ndarray) -> int:
        """Content digest of a vector sample (first 256 valid rows in
        list-major order): row count alone cannot tell a rebuilt store
        with different content apart from the one the fit belongs to."""
        valid = np.flatnonzero(ids.ravel() >= 0)[:256]
        flat = lists.reshape(-1, lists.shape[-1])
        sample = np.ascontiguousarray(flat[valid], np.float32)
        return zlib.crc32(sample.tobytes())

    def save(self, path):
        """Persist centroids + padded id layout (tiny: no raw vectors —
        ``load`` re-gathers them from the store's memmap shards). Written
        atomically (tmp + rename) so a killed build never leaves a torn
        cache."""
        path = Path(path)
        meta = {"n_total": self.n_total, "n_lists": self.n_lists,
                "nprobe": self.nprobe,
                "dim": int(self.centroids.shape[1]),
                "fingerprint": self._fingerprint(np.asarray(self.lists),
                                                 np.asarray(self.ids))}
        tmp = path.with_name(path.name + ".tmp")
        with open(tmp, "wb") as f:
            np.savez(f, centroids=np.asarray(self.centroids),
                     ids=np.asarray(self.ids),
                     meta=np.frombuffer(json.dumps(meta).encode(), np.uint8))
        os.replace(tmp, path)
        return path

    @classmethod
    def load(cls, path, embs) -> "IVFIndex":
        """Rebuild from a ``save``d layout + the store embeddings (any
        array or ``ShardedEmbeddings`` view) — no k-means."""
        path = Path(path)
        with np.load(path) as d:
            meta = json.loads(bytes(d["meta"]).decode())
            centroids = d["centroids"]
            ids = d["ids"]
        st = cls.__new__(cls)
        st.n_total = int(meta["n_total"])
        st.n_lists = int(meta["n_lists"])
        st.nprobe = int(meta["nprobe"])
        st.loaded_from = str(path)
        st.centroids = jnp.asarray(centroids)
        valid = ids >= 0
        rows = ids[valid]
        if hasattr(embs, "iter_shards"):
            vecs = embs.take(rows)       # per-shard row gather, no full copy
        else:
            vecs = np.asarray(embs)[rows]
        buf = np.zeros(ids.shape + (int(meta["dim"]),), np.float32)
        buf[valid] = np.asarray(vecs, np.float32)
        want = meta.get("fingerprint")
        if want is not None and cls._fingerprint(buf, ids) != want:
            raise ValueError(
                f"{path}: persisted IVF fit belongs to different store "
                "content (same row count, different vectors) — rebuild")
        st.lists = jnp.asarray(buf)
        st.ids = jnp.asarray(ids)
        st._search = jax.jit(st._search_impl, static_argnums=(1,))
        return st

    def _search_impl(self, q, k):
        # 1. coarse: score centroids
        cs = q @ self.centroids.T                          # (Q, n_lists)
        _, probe = jax.lax.top_k(cs, self.nprobe)          # (Q, nprobe)
        # 2. gather probed lists and scan
        cand = self.lists[probe]                           # (Q,np,cap,D)
        cand_ids = self.ids[probe]                         # (Q,np,cap)
        s = jnp.einsum("qd,qpcd->qpc", q, cand)
        s = jnp.where(cand_ids < 0, -jnp.inf, s)
        Q = q.shape[0]
        s = s.reshape(Q, -1)
        ci = cand_ids.reshape(Q, -1)
        v, pos = jax.lax.top_k(s, k)
        return v, jnp.take_along_axis(ci, pos, axis=1)

    def search(self, queries: np.ndarray, k: int):
        q = jnp.asarray(np.asarray(queries, np.float32))
        v, i = self._search(q, k)
        return np.asarray(v), np.asarray(i)

    def __len__(self):
        return self.n_total

    def reconstruct(self) -> np.ndarray:
        """The indexed rows, (N, D), rebuilt from the padded list layout
        (row order restored from the stored ids)."""
        lists = np.asarray(self.lists)
        ids = np.asarray(self.ids)
        out = np.zeros((self.n_total, lists.shape[-1]), np.float32)
        valid = ids >= 0
        out[ids[valid]] = lists[valid]
        return out

    def recall_vs_flat(self, queries, k: int = 10) -> float:
        """Mean recall@k of this IVF index against an exact flat scan over
        the same rows. 1.0 means the nprobe pruning lost nothing for these
        queries; ``auto_index`` callers use this to validate an IVF choice.

        The flat reference is built on demand from ``reconstruct()`` and
        discarded — this is a diagnostic, not a serving path, so the index
        doesn't pay a permanent 2x memory cost for it.
        """
        q = np.asarray(queries, np.float32)
        _, flat_ids = FlatIndex(self.reconstruct()).search(q, k)
        _, ivf_ids = self.search(q, k)
        hits = [len(set(f.tolist()) & set(i.tolist())) / k
                for f, i in zip(flat_ids, ivf_ids)]
        return float(np.mean(hits))


# ---------------------------------------------------------------------------
# Incremental dedup index (offline pipeline)
# ---------------------------------------------------------------------------


class IncrementalIndex:
    """Append-only max-similarity index for the offline dedup loop (§3.2 at
    paper scale): ``add(embs)`` + ``max_sim(queries)``.

    Replaces the sequential generator's quadratic scan (re-``concatenate``
    the full embedding matrix + full-matrix matmul per candidate):

    * **flat** (≤ ``flat_max_rows``): rows live in one amortized-doubling
      buffer; ``max_sim`` is a single blocked matmul per wave.
    * **ivf** (above it): rows are assigned to their nearest (max-dot)
      centroid on ``add`` and ``max_sim`` probes only the top-``nprobe``
      lists — sub-linear, approximate like any ANN dedup (the paper's
      DiskANN dedup is too). Assignment and probing use the same
      inner-product metric, so an exact duplicate always probes the list
      that holds its twin.

    Re-clustering is amortized: centroids are refit (k-means over all rows
    so far) whenever the row count crosses ``flat_max_rows * 2^k``. In the
    default deterministic mode, ``add`` splits batches exactly at those
    thresholds, so the index state is a pure function of the row sequence —
    independent of how adds were batched. That is what makes a kill-and-
    resume rebuild (re-adding shard-at-a-time) bit-identical to the
    uninterrupted build. ``background=True`` moves refits to a thread for
    throughput, giving up that determinism.
    """

    def __init__(self, dim: int, *, flat_max_rows: int = FLAT_MAX_ROWS,
                 probe_frac: float = 1 / 16, seed: int = 0,
                 background: bool = False):
        self.dim = dim
        self.flat_max_rows = flat_max_rows
        self.probe_frac = probe_frac
        self.seed = seed
        self.background = background
        self._buf = np.empty((1024, dim), np.float32)
        self._n = 0
        self._next_refit = flat_max_rows
        self.centroids: Optional[np.ndarray] = None     # (L, D) in ivf mode
        self._list_ids: List[np.ndarray] = []           # ragged int32 lists
        self._list_n: Optional[np.ndarray] = None
        self._lock = threading.Lock()
        self._refit_thread: Optional[threading.Thread] = None
        self.refits = 0

    def __len__(self) -> int:
        return self._n

    @property
    def mode(self) -> str:
        return "flat" if self.centroids is None else "ivf"

    @property
    def nprobe(self) -> int:
        """Duplicates share their twin's top-1 list by construction (same
        inner-product metric for assignment and probing), so a thin probe
        fan suffices for dedup — min 4 lists for near-boundary cases."""
        n_lists = len(self._list_ids)
        return max(1, min(n_lists,
                          max(4, int(round(n_lists * self.probe_frac)))))

    # -- append ---------------------------------------------------------------
    def add(self, embs: np.ndarray):
        embs = np.asarray(embs, np.float32)
        if embs.ndim == 1:
            embs = embs[None, :]
        if self.background:
            self._append(embs)
            if self._n >= self._next_refit and (
                    self._refit_thread is None
                    or not self._refit_thread.is_alive()):
                self._next_refit *= 2
                self._refit_thread = threading.Thread(
                    target=self._refit, daemon=True)
                self._refit_thread.start()
            return
        # deterministic mode: split the batch at refit thresholds so the
        # fit always sees exactly `threshold` rows, however adds arrive
        while len(embs):
            room = self._next_refit - self._n
            head, embs = embs[:room], embs[room:]
            self._append(head)
            if self._n == self._next_refit:
                self._refit()
                self._next_refit *= 2

    def _grow(self, need: int):
        cap = self._buf.shape[0]
        if self._n + need <= cap:
            return
        while cap < self._n + need:
            cap *= 2
        new = np.empty((cap, self.dim), np.float32)
        new[:self._n] = self._buf[:self._n]
        self._buf = new

    def _append(self, embs: np.ndarray):
        with self._lock:
            self._grow(len(embs))
            lo = self._n
            self._buf[lo:lo + len(embs)] = embs
            self._n += len(embs)
            if self.centroids is not None:
                assign = np.argmax(embs @ self.centroids.T, axis=1)
                for j, a in enumerate(assign):
                    self._list_append(int(a), lo + j)

    def _list_append(self, a: int, row: int):
        ids, n = self._list_ids[a], int(self._list_n[a])
        if n == ids.shape[0]:
            grown = np.empty(max(2 * n, 8), np.int32)
            grown[:n] = ids
            self._list_ids[a] = ids = grown
        ids[n] = row
        self._list_n[a] += 1

    def _refit(self):
        """K-means over all rows so far; rebuild the assignment lists.
        In background mode the fit runs without the lock (appends continue
        against the old centroids) and only the swap is locked."""
        with self._lock:
            n0 = self._n
            x = self._buf[:n0].copy() if self.background \
                else self._buf[:n0]
        n_lists, _ = ivf_params(n0)
        cent, assign = kmeans(jnp.asarray(x), n_lists, seed=self.seed)
        cent = np.asarray(cent)
        with self._lock:
            # re-assign by max inner product (the probe metric) so a row
            # is always found in the list its duplicates will probe first
            assign = np.argmax(self._buf[:self._n] @ cent.T, axis=1)
            self.centroids = cent
            counts = np.bincount(assign, minlength=cent.shape[0])
            self._list_ids = [np.empty(max(int(c), 8), np.int32)
                              for c in counts]
            self._list_n = np.zeros(cent.shape[0], np.int64)
            order = np.argsort(assign, kind="stable")
            sorted_assign = assign[order]
            starts = np.searchsorted(sorted_assign,
                                     np.arange(cent.shape[0]))
            ends = np.searchsorted(sorted_assign,
                                   np.arange(cent.shape[0]), side="right")
            for a in range(cent.shape[0]):
                rows = order[starts[a]:ends[a]]
                self._list_ids[a][:len(rows)] = rows
                self._list_n[a] = len(rows)
            self.refits += 1

    def drain(self):
        """Join an in-flight background refit (no-op otherwise)."""
        if self._refit_thread is not None:
            self._refit_thread.join()

    # -- query ----------------------------------------------------------------
    def max_sim(self, queries: np.ndarray) -> np.ndarray:
        """Max inner product of each query against every stored row
        (-inf when empty). Exact in flat mode; nprobe-approximate in ivf."""
        q = np.asarray(queries, np.float32)
        if q.ndim == 1:
            q = q[None, :]
        with self._lock:
            n = self._n
            if n == 0:
                return np.full(q.shape[0], -np.inf, np.float32)
            if self.centroids is None:
                return (q @ self._buf[:n].T).max(axis=1)
            cent, buf = self.centroids, self._buf
            nprobe = self.nprobe
            Q = q.shape[0]
            cs = q @ cent.T
            probes = np.argpartition(cs, -nprobe, axis=1)[:, -nprobe:]
            # invert (query -> lists) to (list -> queries): each probed
            # list is gathered ONCE per call and scanned as one matmul
            # against every query that probes it — per-query gathers were
            # the offline-build bottleneck at paper scale
            flat = probes.ravel()
            qidx = np.repeat(np.arange(Q), nprobe)
            order = np.argsort(flat, kind="stable")
            flat, qidx = flat[order], qidx[order]
            bounds = np.searchsorted(flat, np.arange(len(self._list_ids)))
            out = np.full(Q, -np.inf, np.float32)
            for a in np.unique(flat):
                lo = bounds[a]
                hi = bounds[a + 1] if a + 1 < len(bounds) else len(flat)
                n = int(self._list_n[a])
                if n == 0:
                    continue
                qs = qidx[lo:hi]
                s = (buf[self._list_ids[a][:n]] @ q[qs].T).max(axis=0)
                np.maximum.at(out, qs, s)
            return out


class ShardedIndex:
    """Mesh-sharded exact MIPS: rows over ``shard_axis``, distributed top-k.

    Quantized views shard the int8 values + per-row scales as-is (4x less
    HBM per device); queries are quantized like the flat kernel layout's,
    and each local scan scores its int8 shard and dequantizes in place
    (distributed/topk.py), so both tiers return the same scores. Float
    inputs shard fp32."""

    def __init__(self, embs: np.ndarray, mesh, shard_axis: str = "model"):
        from jax.sharding import NamedSharding, PartitionSpec as P
        n_sh = mesh.shape[shard_axis]
        self.mesh = mesh
        self.shard_axis = shard_axis
        self.scales = None
        row_sh = NamedSharding(mesh, P(shard_axis, None))
        if getattr(embs, "is_quantized", False):
            vals, scales = embs.take_q(np.arange(embs.shape[0]))
            N, D = vals.shape
            pad = (-N) % n_sh
            if pad:       # zero rows score 0; masked out via n_real
                vals = np.concatenate(
                    [vals, np.zeros((pad, D), np.int8)], axis=0)
                scales = np.concatenate(
                    [scales, np.ones(pad, np.float32)])
            self.embs = jax.device_put(jnp.asarray(vals), row_sh)
            self.scales = jax.device_put(
                jnp.asarray(scales), NamedSharding(mesh, P(shard_axis)))
            self.n_real = N
        else:
            embs = np.asarray(embs)
            N, D = embs.shape
            pad = (-N) % n_sh
            if pad:
                embs = np.concatenate(
                    [embs, np.full((pad, D), -1e4, embs.dtype)], axis=0)
            self.n_real = N
            self.embs = jax.device_put(
                jnp.asarray(np.asarray(embs, np.float32)), row_sh)

    def search(self, queries: np.ndarray, k: int):
        from repro.distributed.topk import sharded_mips_topk
        q = np.asarray(queries, np.float32)
        if self.scales is None:
            v, i = sharded_mips_topk(jnp.asarray(q), self.embs, k,
                                     mesh=self.mesh,
                                     shard_axis=self.shard_axis)
        else:
            q8, qs = quantize_rows(q)
            v, i = sharded_mips_topk(
                jnp.asarray(q8), self.embs, k, mesh=self.mesh,
                shard_axis=self.shard_axis, scales=self.scales,
                n_real=self.n_real, q_scale=jnp.asarray(qs))
        return np.asarray(v), np.asarray(i)

    def __len__(self):
        return self.n_real


# ---------------------------------------------------------------------------
# Tier auto-selection
# ---------------------------------------------------------------------------


def select_tier(n_rows: int, mesh_axis_size: int = 1, *,
                flat_max_rows: int = FLAT_MAX_ROWS,
                shard_min_rows: int = SHARD_MIN_ROWS) -> str:
    """Pure tier decision: ``"flat" | "ivf" | "sharded"``.

    Separated from ``auto_index`` so the boundary logic is unit-testable
    without building real indexes (or a real multi-device mesh).
    """
    if n_rows <= 0:
        raise ValueError("cannot index an empty store")
    if mesh_axis_size > 1 and n_rows >= shard_min_rows:
        return "sharded"
    if n_rows <= flat_max_rows:
        return "flat"
    return "ivf"


def ivf_params(n_rows: int) -> Tuple[int, int]:
    """(n_lists, nprobe) heuristic: sqrt-N lists, probe ~1/8 of them (at
    least 8) — keeps the scanned fraction roughly constant as N grows."""
    n_lists = max(16, int(round(float(n_rows) ** 0.5)))
    nprobe = max(8, n_lists // 8)
    return n_lists, min(nprobe, n_lists)


IVF_CACHE_NAME = "index_ivf.npz"


def auto_index(store, mesh=None, *, shard_axis: str = "model",
               use_kernel: Optional[bool] = None,
               flat_max_rows: int = FLAT_MAX_ROWS,
               shard_min_rows: int = SHARD_MIN_ROWS, seed: int = 0,
               cache_dir=None):
    """Build the right index tier for ``store`` (a PrecomputedStore, or any
    object with ``.embeddings()``, or a raw (N, D) array).

    ``use_kernel=None`` routes the flat scan through the Pallas mips_topk
    kernel when running on a real TPU and keeps the plain jnp path (faster
    than interpret mode) on CPU.

    ``cache_dir`` (typically the store root) persists the IVF k-means
    product: a matching cache loads (no k-means); a stale or missing one
    rebuilds and re-saves. Flat and sharded tiers have no build product to
    cache, so the option is a no-op there.
    """
    if hasattr(store, "embeddings"):
        embs = store.embeddings()
    else:
        embs = np.asarray(store, np.float32)
    n_rows = int(embs.shape[0])
    axis_size = 1
    if mesh is not None:
        try:
            axis_size = int(mesh.shape[shard_axis])
        except (KeyError, TypeError):
            axis_size = 1
    tier = select_tier(n_rows, axis_size,
                       flat_max_rows=flat_max_rows,
                       shard_min_rows=shard_min_rows)
    is_store = hasattr(store, "embeddings")
    if tier == "sharded":
        return ShardedIndex(embs, mesh, shard_axis=shard_axis)
    if tier == "ivf":
        n_lists, nprobe = ivf_params(n_rows)
        cache = Path(cache_dir) / IVF_CACHE_NAME if cache_dir else None
        if cache is not None and cache.exists():
            try:
                idx = IVFIndex.load(cache, embs)
                if (idx.n_total == n_rows and idx.n_lists == n_lists
                        and idx.nprobe == nprobe):
                    return idx
            except Exception:
                pass              # unreadable/stale cache: rebuild below
        dev = cached_device_store(store) if is_store else None
        idx = IVFIndex(embs, n_lists=n_lists, nprobe=nprobe, seed=seed,
                       device=dev)
        if cache is not None:
            idx.save(cache)
        return idx
    layout = "auto" if use_kernel is None else \
        ("kernel" if use_kernel else "gemm")
    dev = device_store_for(store, layout=layout) if is_store \
        else DeviceStore(embs, layout=layout)
    return FlatIndex(device=dev, use_kernel=dev.layout == "kernel")
