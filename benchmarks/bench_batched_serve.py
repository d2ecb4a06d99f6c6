"""Batched StorInfer serving throughput, three sections:

1. **batched vs sequential** — `StorInfer.query` (the paper's Fig-2 loop)
   vs `StorInfer.query_batch` on the SAME system; amortization is the
   whole story (one embed + one MIPS dispatch per microbatch). Floor:
   >= 4x queries/sec at batch 32.
2. **pipelined serving** — a mixed 50/50 hit/miss stream through the
   staged `ServingPipeline` (facade `serve()`/`submit()`, a real
   smoke-arch engine decoding the misses). Measures the hit-latency
   decoupling the paper's "instantly returns the stored response" story
   requires: hits resolve at MIPS-search time, never waiting on any miss
   decode. Floor: hit-path p50 <= 0.5x miss-path p50 (enforced in smoke
   mode too — the margin is orders of magnitude when decode is real).
3. **quantized flat scan** — the device-resident int8 path vs the pre-PR
   fp32 flat scan (kept verbatim below as `_LegacyFlatIndex`): same rows,
   serving-mix queries, N >= 100K in full mode. Floors: top-1 agreement
   with exact fp32 >= 0.99 on would-hit queries, int8 store bytes <= 30%
   of the fp32 store, and (full mode, where N is large enough for the
   bandwidth effect to dominate timing noise) scan throughput >= the
   configured floor (default 1.4x tripwire; measured ~2x at N=100K).

Emits experiments/bench/BENCH_batched_serve.json AND a repo-root
BENCH_serve.json (the machine-readable perf-trajectory point CI uploads,
now carrying hit/miss p50+p99 for the pipelined path).
Exits non-zero below any floor.

  PYTHONPATH=src python benchmarks/bench_batched_serve.py [--smoke]
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import time
from pathlib import Path

_ROOT = Path(__file__).resolve().parents[1]
for p in (str(_ROOT), str(_ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import out_write
from repro.api import EngineCfg, StorInfer, SystemCfg, make_embedder, \
    make_index, tier_of
from repro.core.runtime import BatchedRuntimeCfg
from repro.launch.compile_cache import enable_compile_cache
from repro.core.store import PrecomputedStore


def build_synth_store(root, emb, n_rows: int, batch: int = 2048):
    """Write synthetic query/response pairs to ``root`` and close the
    store (reopen via ``StorInfer.open``); embeddings come from the real
    embedder so sequential and batched paths search identical data."""
    store = PrecomputedStore(root, dim=emb.dim)
    for lo in range(0, n_rows, batch):
        hi = min(lo + batch, n_rows)
        qs = [f"synthetic question {i} about topic {i % 97} and "
              f"entity {i % 31}" for i in range(lo, hi)]
        rs = [f"stored answer number {i}." for i in range(lo, hi)]
        store.add_batch(emb.encode(qs), qs, rs)
    store.close()


def user_queries(n: int, n_store: int, hit_frac: float = 0.5, seed: int = 0):
    rng = np.random.default_rng(seed)
    out = []
    for j in range(n):
        if rng.random() < hit_frac:
            i = int(rng.integers(0, n_store))
            out.append(f"synthetic question {i} about topic {i % 97} and "
                       f"entity {i % 31}")
        else:
            out.append(f"novel unseen query {j} zebra {rng.integers(1e6)}")
    return out


def pcts(lat_s):
    a = np.asarray(lat_s)
    return {"p50_ms": float(np.percentile(a, 50) * 1e3),
            "p99_ms": float(np.percentile(a, 99) * 1e3),
            "mean_ms": float(a.mean() * 1e3)}


# ---------------------------------------------------------------------------
# Section 2: pipelined serving — hit p50 decoupled from miss decode
# ---------------------------------------------------------------------------


def bench_pipelined_serving(n_store, n_q, batch, s_th, ratio_floor,
                            decode_slots=4, max_new=8, seed=1):
    """Mixed 50/50 hit/miss stream through the staged pipeline end to end
    (facade ``serve()``/``submit()``) with a real smoke-arch engine behind
    the misses. The whole point of the stage decoupling: hit futures
    resolve at MIPS-search time, so hit p50 must sit far below miss p50
    instead of being gated by the slowest miss in the microbatch."""
    with tempfile.TemporaryDirectory() as td:
        build_synth_store(td, make_embedder("hash"), n_store)
        cfg = SystemCfg(
            s_th_run=s_th,
            engine=EngineCfg(smoke=True, max_len=96, chunk=8),
            batched=BatchedRuntimeCfg(max_batch=batch, max_wait_s=0.002),
            decode_slots=decode_slots,
            queue_depth=max(64, 2 * n_q))
        queries = user_queries(n_q, n_store, hit_frac=0.5, seed=seed)
        with StorInfer.open(td, cfg) as si:
            with si.serve():
                # warm the jit caches (search shape + prefill/decode) on a
                # throwaway hit + miss before timing anything
                warm = [si.submit("synthetic question 0 about topic 0 "
                                  "and entity 0", max_new=max_new),
                        si.submit("warmup novel zebra query xyz",
                                  max_new=max_new)]
                [f.result(timeout=600) for f in warm]

                t0 = time.perf_counter()
                futs = [si.submit(q, max_new=max_new) for q in queries]
                results = [f.result(timeout=600) for f in futs]
                wall = time.perf_counter() - t0
            snap = si.stats().pipeline

        hit_lat = [r.latency_s for r in results if r.hit]
        miss_lat = [r.latency_s for r in results if not r.hit]
        assert hit_lat and miss_lat, \
            "mixed workload degenerated to one class — floor is vacuous"
        hit_p, miss_p = pcts(hit_lat), pcts(miss_lat)
        ratio = hit_p["p50_ms"] / miss_p["p50_ms"]
        section = {
            "n_store": n_store, "n_queries": n_q,
            "decode_slots": decode_slots, "max_new": max_new,
            "hit_rate": len(hit_lat) / n_q,
            "hit": hit_p, "miss": miss_p,
            "p50_ratio": ratio, "ratio_floor": ratio_floor,
            "qps": n_q / wall,
            "stages": snap["stages"],
            "decode_reuse": snap.get("decode_slots"),
        }
        print(f"pipelined serving: store={n_store} queries={n_q} "
              f"(hit_rate={section['hit_rate']:.2f}) "
              f"decode_slots={decode_slots}")
        print(f"  hit:  p50={hit_p['p50_ms']:8.2f}ms "
              f"p99={hit_p['p99_ms']:8.2f}ms  (n={len(hit_lat)})")
        print(f"  miss: p50={miss_p['p50_ms']:8.2f}ms "
              f"p99={miss_p['p99_ms']:8.2f}ms  (n={len(miss_lat)})")
        print(f"  hit/miss p50 ratio: {ratio:.3f} "
              f"(floor {ratio_floor}) — {n_q / wall:.1f} q/s end-to-end")
        reuse = section["decode_reuse"] or {}
        if reuse:
            print(f"  decode slots: {reuse['slots']} slots served "
                  f"{reuse['admitted']} misses over {reuse['waves']} waves")

        failures = []
        if ratio > ratio_floor:
            failures.append(
                f"pipelined hit p50 {hit_p['p50_ms']:.2f}ms is "
                f"{ratio:.2f}x miss p50 {miss_p['p50_ms']:.2f}ms "
                f"(floor {ratio_floor}x) — hits are gated by miss decode")
        return section, failures


# ---------------------------------------------------------------------------
# Section 3: device-resident int8 flat scan vs the pre-PR fp32 path
# ---------------------------------------------------------------------------


class _LegacyFlatIndex:
    """The pre-PR FlatIndex scan, verbatim: fp32 (N, D) resident,
    jit(q @ x.T + top_k) per search. Kept here as the measured baseline
    so the reported speedup is against the REAL old code path, not a
    strawman."""

    def __init__(self, embs):
        self.embs = jnp.asarray(np.asarray(embs, np.float32))
        self._search = jax.jit(self._impl, static_argnums=(2,))

    @staticmethod
    def _impl(q, embs, k):
        return jax.lax.top_k(q @ embs.T, k)

    def search(self, queries, k):
        q = jnp.asarray(np.asarray(queries, np.float32))
        v, i = self._search(q, self.embs, k)
        return np.asarray(v), np.asarray(i)


def _scan_qps(index, queries, batch, reps=3):
    """Best-of-``reps`` queries/sec over the full query set (min total
    wall-clock de-noises a shared box; the jit cache is warmed first)."""
    index.search(queries[:batch], 1)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for lo in range(0, len(queries), batch):
            index.search(queries[lo:lo + batch], 1)
        best = min(best, time.perf_counter() - t0)
    return len(queries) / best


def _fill(store, embs, batch=8192):
    for lo in range(0, embs.shape[0], batch):
        hi = min(lo + batch, embs.shape[0])
        store.add_batch(embs[lo:hi],
                        [f"q{i}" for i in range(lo, hi)],
                        [f"r{i}" for i in range(lo, hi)])
    store.close()


def bench_quantized_flat(n_rows, n_q, batch, s_th, speedup_floor,
                         enforce_speedup, seed=0):
    rng = np.random.default_rng(seed)
    dim = 384
    embs = rng.normal(size=(n_rows, dim)).astype(np.float32)
    embs /= np.linalg.norm(embs, axis=1, keepdims=True)
    # serving mix: half near-duplicates of stored rows (the hit regime the
    # paper's threshold race depends on), half novel queries. Noise sigma
    # 0.01 keeps the duplicates ABOVE s_th (cos ~ 1/sqrt(1 + 0.01^2 * D)
    # ~ 0.98 at D=384) so the would-hit recall floor below actually
    # compares queries — 0.05 would push every duplicate under 0.9 and
    # make the floor vacuously true
    n_hit = n_q // 2
    hit_q = embs[rng.integers(0, n_rows, n_hit)] \
        + 0.01 * rng.normal(size=(n_hit, dim)).astype(np.float32)
    nov_q = rng.normal(size=(n_q - n_hit, dim)).astype(np.float32)
    queries = np.concatenate([hit_q, nov_q]).astype(np.float32)
    queries /= np.linalg.norm(queries, axis=1, keepdims=True)

    with tempfile.TemporaryDirectory() as td:
        td = Path(td)
        _fill(PrecomputedStore(td / "fp32", dim=dim, emb_dtype="float32"),
              embs)
        _fill(PrecomputedStore(td / "int8", dim=dim, emb_dtype="int8"),
              embs)
        st32 = PrecomputedStore.open_(td / "fp32")
        st8 = PrecomputedStore.open_(td / "int8")
        bytes32 = st32.storage_bytes()["index_bytes"]
        bytes8 = st8.storage_bytes()["index_bytes"]

        legacy = _LegacyFlatIndex(embs)
        quant = make_index("flat", st8)       # DeviceStore-resident int8

        legacy_qps = _scan_qps(legacy, queries, batch)
        quant_qps = _scan_qps(quant, queries, batch)

        # fidelity: exact fp32 scores from the legacy arm ARE the oracle
        v32, i32 = legacy.search(queries, 1)
        v8, i8 = quant.search(queries, 1)
        would_hit = v32[:, 0] >= s_th
        n_would_hit = int(would_hit.sum())
        recall_hits = float((i8[would_hit, 0] ==
                             i32[would_hit, 0]).mean()) \
            if n_would_hit else float("nan")
        recall_all = float((i8[:, 0] == i32[:, 0]).mean())
        hit_flip = float((np.asarray(v8[:, 0] >= s_th) !=
                          would_hit).mean())
        st32.close()
        st8.close()

    speedup = quant_qps / legacy_qps
    bytes_ratio = bytes8 / bytes32
    section = {
        "n_rows": n_rows, "n_queries": n_q, "batch": batch, "dim": dim,
        "s_th_run": s_th,
        "resident": quant.dev.layout,
        "legacy_fp32_qps": legacy_qps, "int8_qps": quant_qps,
        "scan_speedup": speedup, "speedup_floor": speedup_floor,
        "speedup_enforced": bool(enforce_speedup),
        "recall_at1_hits": recall_hits, "n_would_hit": n_would_hit,
        "recall_at1_all": recall_all,
        "hit_decision_flip_rate": hit_flip,
        "int8_bytes": int(bytes8), "fp32_bytes": int(bytes32),
        "bytes_ratio": bytes_ratio,
    }
    print(f"quantized flat scan: N={n_rows} batch={batch} "
          f"({section['resident']} residency)")
    print(f"  legacy fp32: {legacy_qps:8.1f} q/s   int8 device-resident: "
          f"{quant_qps:8.1f} q/s   speedup {speedup:.2f}x "
          f"(floor {speedup_floor}x"
          f"{', enforced' if enforce_speedup else ', report-only'})")
    print(f"  recall@1 vs fp32: {recall_hits:.4f} on {n_would_hit} "
          f"would-hit queries (floor 0.99), {recall_all:.4f} overall; "
          f"hit-decision flips {hit_flip:.4f}")
    print(f"  store bytes: int8 {bytes8 / 1e6:.1f} MB vs fp32 "
          f"{bytes32 / 1e6:.1f} MB = {bytes_ratio:.3f} (floor 0.30)")

    failures = []
    # guard against a vacuous floor: the duplicate half of the mix must
    # actually clear the threshold for the recall comparison to exist
    if n_would_hit < n_hit // 2:
        failures.append(
            f"only {n_would_hit}/{n_hit} duplicate queries cleared "
            f"s_th={s_th} — the recall floor compared (almost) nothing")
    if not (recall_hits >= 0.99):          # NaN fails too
        failures.append(f"recall@1 {recall_hits:.4f} < 0.99")
    if bytes_ratio > 0.30:
        failures.append(f"bytes ratio {bytes_ratio:.3f} > 0.30")
    if enforce_speedup and speedup < speedup_floor:
        failures.append(f"scan speedup {speedup:.2f}x < {speedup_floor}x")
    return section, failures


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small store/query count for CI")
    ap.add_argument("--n-store", type=int, default=None)
    ap.add_argument("--n-queries", type=int, default=None)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--quant-rows", type=int, default=None,
                    help="rows for the quantized flat-scan section "
                         "(default 100K full / 8K smoke)")
    ap.add_argument("--quant-speedup-floor", type=float, default=1.4,
                    help="int8-vs-legacy scan throughput floor, enforced "
                         "in full mode (tripwire below the ~2x measured "
                         "at N=100K)")
    ap.add_argument("--pipeline-queries", type=int, default=None,
                    help="mixed-stream size for the pipelined section "
                         "(default 64 full / 24 smoke)")
    ap.add_argument("--pipeline-ratio-floor", type=float, default=0.5,
                    help="hit p50 must be <= this fraction of miss p50 "
                         "through the staged pipeline (enforced always)")
    ap.add_argument("--decode-slots", type=int, default=4,
                    help="persistent continuous-batching decode slots for "
                         "the pipelined section")
    args = ap.parse_args(argv)

    n_store = args.n_store or (2000 if args.smoke else 20000)
    n_q = args.n_queries or (128 if args.smoke else 512)
    B = args.batch
    quant_rows = args.quant_rows or (8000 if args.smoke else 100_000)
    pipe_q = args.pipeline_queries or (24 if args.smoke else 64)

    with tempfile.TemporaryDirectory() as td:
        build_synth_store(td, make_embedder("hash"), n_store)
        cfg = SystemCfg(s_th_run=0.9,
                        batched=BatchedRuntimeCfg(max_batch=B))
        with StorInfer.open(td, cfg) as si:
            tier = tier_of(si.index)
            queries = user_queries(n_q, n_store)

            # warm the jit caches on both paths before timing
            si.query(queries[0])
            si.query_batch(queries[:B])

            # -- sequential: the paper's one-at-a-time race loop -----------
            seq_lat = []
            t0 = time.perf_counter()
            seq_hits = 0
            for q in queries:
                t1 = time.perf_counter()
                r = si.query(q)
                seq_lat.append(time.perf_counter() - t1)
                seq_hits += int(r.hit)
            seq_total = time.perf_counter() - t0
            seq_qps = n_q / seq_total

            # -- batched: microbatches of B through one index dispatch -----
            bat_lat = []
            t0 = time.perf_counter()
            bat_hits = 0
            for lo in range(0, n_q, B):
                chunk = queries[lo:lo + B]
                t1 = time.perf_counter()
                rs = si.query_batch(chunk)
                dt = time.perf_counter() - t1
                bat_lat.extend([dt] * len(chunk))  # each waits its batch
                bat_hits += sum(r.hit for r in rs)
            bat_total = time.perf_counter() - t0
            bat_qps = n_q / bat_total

        assert seq_hits == bat_hits, (seq_hits, bat_hits)
        speedup = bat_qps / seq_qps
        payload = {
            "n_store": n_store, "n_queries": n_q, "batch": B,
            "index_tier": tier, "hit_rate": seq_hits / n_q,
            "sequential": {"qps": seq_qps, **pcts(seq_lat)},
            "batched": {"qps": bat_qps, **pcts(bat_lat)},
            "speedup_qps": speedup,
            "smoke": bool(args.smoke),
        }
        print(f"store={n_store} ({tier})  queries={n_q}  batch={B}")
        print(f"sequential: {seq_qps:8.1f} q/s  "
              f"p50={payload['sequential']['p50_ms']:.2f}ms "
              f"p99={payload['sequential']['p99_ms']:.2f}ms")
        print(f"batched:    {bat_qps:8.1f} q/s  "
              f"p50={payload['batched']['p50_ms']:.2f}ms "
              f"p99={payload['batched']['p99_ms']:.2f}ms")
        print(f"speedup: {speedup:.1f}x (floor 4x)")

    failures = []
    if speedup < 4.0:
        failures.append(
            f"batched speedup {speedup:.1f}x below the 4x floor")

    # hit-latency decoupling through the staged pipeline (floor enforced
    # in smoke mode too — real decode keeps the margin wide)
    payload["pipelined"], pf = bench_pipelined_serving(
        n_store=2000 if args.smoke else 4000, n_q=pipe_q, batch=B,
        s_th=0.9, ratio_floor=args.pipeline_ratio_floor,
        decode_slots=args.decode_slots,
        max_new=8 if args.smoke else 16)
    failures += pf

    # the N>=100K bandwidth effect is what the floor measures; at smoke
    # scale the section still runs (recall + bytes floors enforced) but
    # the throughput ratio is report-only
    payload["quantized_flat"], qf = bench_quantized_flat(
        quant_rows, n_q=max(n_q, 128), batch=B, s_th=0.9,
        speedup_floor=args.quant_speedup_floor,
        enforce_speedup=not args.smoke)
    failures += qf

    out_write("BENCH_batched_serve", payload, root_name="BENCH_serve")
    for f in failures:
        print(f"WARNING: {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
