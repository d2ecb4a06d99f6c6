"""Offline precompute pipeline benchmark: the paper-scale store build.

Four checks, emitted as one BENCH_precompute.json point:

  1. **speedup** — batched `PrecomputePipeline` (wave 32) vs the sequential
     `QueryGenerator.generate` reference on the same KB/target/seed.
     Acceptance floor: >= 3x pairs/sec.
  2. **scale** — a large deduplicated store build through `StorInfer.build`
     (>= 100K rows in full mode; scaled down under --smoke), reporting
     pairs/sec, discard rate, and the storage split.
  3. **index cache** — `make_index("auto", store, cache_dir=store.root)`
     twice: the first call fits + persists IVF k-means, the second must
     LOAD it (no k-means — asserted, not just timed) and return identical
     search results.
  4. **resume** — the build is killed mid-flight and resumed; the resumed
     store must be byte-identical (text, offsets, every embedding shard)
     to an uninterrupted run.

  PYTHONPATH=src python benchmarks/bench_precompute.py [--smoke]
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

import numpy as np

from benchmarks.common import out_write
from repro.api import StorInfer, SystemCfg, make_embedder, make_index, \
    make_pipeline
from repro.core.generator import (GenCfg, QueryGenerator, SyntheticOracleLM,
                                  chunk_key)
from repro.core.kb import build_kb
from repro.core.precompute import BuildKilled, PrecomputeCfg
from repro.launch.compile_cache import enable_compile_cache


def kb_env(n_docs: int, seed: int = 0):
    from repro.core.tokenizer import Tokenizer
    kb = build_kb("squad", seed=seed, n_docs=n_docs)
    tok = Tokenizer.from_texts([d.text() for d in kb.docs])
    chunks = [chunk_key(d.doc_id, d.text()) for d in kb.docs]
    return kb, tok, chunks


def bench_speedup(n_pairs: int, wave: int, n_docs: int = 60):
    kb, tok, chunks = kb_env(n_docs=n_docs)
    emb = make_embedder("hash")

    t0 = time.perf_counter()
    gen = QueryGenerator(SyntheticOracleLM(kb), emb, tok, GenCfg(dedup=True))
    sq, _, _, sstats = gen.generate(chunks, n_pairs, seed=0)
    seq_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    pipe = make_pipeline(SystemCfg(precompute=PrecomputeCfg(wave=wave)),
                         SyntheticOracleLM(kb), tok)
    bq, _, be, bstats = pipe.run(chunks, n_pairs, seed=0)
    bat_s = time.perf_counter() - t0

    assert len(sq) == len(bq) == n_pairs, (len(sq), len(bq))
    sims = be @ be.T - np.eye(len(be))
    assert sims.max() < 0.99, "pipeline accepted a near-duplicate"
    return {
        "n_pairs": n_pairs, "wave": wave,
        "sequential": {"seconds": seq_s, "pairs_per_sec": n_pairs / seq_s,
                       "discarded": sstats.discarded},
        "batched": {"seconds": bat_s, "pairs_per_sec": n_pairs / bat_s,
                    "discarded": bstats.discarded},
        "speedup": seq_s / bat_s,
    }


def bench_scale(root: Path, n_rows: int, wave: int, n_docs: int,
                background: bool):
    kb, tok, chunks = kb_env(n_docs=n_docs)
    # index="none": the serving index is fit (and timed) separately by
    # bench_index_cache, which asserts the first fit does NOT hit a cache
    cfg = SystemCfg(index="none", precompute=PrecomputeCfg(
        wave=wave, background_recluster=background))
    t0 = time.perf_counter()
    si = StorInfer.build(kb, cfg, root, n_pairs=n_rows, tokenizer=tok,
                         seed=0)
    build_s = time.perf_counter() - t0
    stats = si.build_stats
    sb = si.store.storage_bytes()
    out = {
        "rows": si.store.count, "seconds": build_s,
        "pairs_per_sec": stats.generated / build_s,
        "discarded": stats.discarded,
        "dedup_index_mode": stats.index_mode,
        "store_mb": sb["total_bytes"] / 1e6,
        "embeddings_mb": sb["index_bytes"] / 1e6,
        "metadata_mb": sb["metadata_bytes"] / 1e6,
    }
    return si.store, out


def bench_index_cache(store, flat_max_rows: int):
    t0 = time.perf_counter()
    built = make_index("auto", store, cache_dir=store.root,
                       flat_max_rows=flat_max_rows)
    build_s = time.perf_counter() - t0
    assert built.loaded_from is None, "first build unexpectedly hit a cache"

    t0 = time.perf_counter()
    loaded = make_index("auto", store, cache_dir=store.root,
                        flat_max_rows=flat_max_rows)
    load_s = time.perf_counter() - t0
    assert loaded.loaded_from is not None, \
        "reopen re-ran k-means instead of loading the persisted index"
    q = np.asarray(store.embeddings()[:16], np.float32)
    vb, ib = built.search(q, 5)
    vl, il = loaded.search(q, 5)
    assert np.allclose(vb, vl) and (ib == il).all(), \
        "cached index disagrees with the fresh build"
    return {"build_seconds": build_s, "load_seconds": load_s,
            "load_speedup": build_s / max(load_s, 1e-9),
            "n_lists": built.n_lists}


def bench_resume(td: Path, n_rows: int, wave: int):
    kb, tok, chunks = kb_env(n_docs=20)
    cfg = SystemCfg(index="none", shard_rows=256,
                    precompute=PrecomputeCfg(wave=wave,
                                             checkpoint_every=4))

    A, B = td / "uninterrupted", td / "resumed"
    StorInfer.build(kb, cfg, A, n_pairs=n_rows, tokenizer=tok,
                    seed=5).close()

    try:
        # the kill: StorInfer.build aborts the store handle (buffers reach
        # disk, nothing past the last checkpoint commits) and re-raises
        StorInfer.build(kb, cfg, B, n_pairs=n_rows, tokenizer=tok, seed=5,
                        _kill_after_waves=(n_rows // wave) // 2 + 1)
    except BuildKilled:
        pass
    si = StorInfer.build(kb, cfg, B, n_pairs=n_rows, tokenizer=tok, seed=5)
    stats = si.build_stats
    si.close()

    files = ["text.jsonl", "offsets.npy"] + sorted(
        p.name for p in A.glob("emb_*.npy"))
    identical = all((A / f).read_bytes() == (B / f).read_bytes()
                    for f in files)
    return {"rows": n_rows, "resumed_from": stats.resumed_rows,
            "files_compared": len(files), "identical": identical}


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small targets for CI")
    ap.add_argument("--rows", type=int, default=None,
                    help="scale-build row target (default: 100000, or 4000 "
                         "under --smoke)")
    ap.add_argument("--wave", type=int, default=32)
    ap.add_argument("--background-recluster", action="store_true",
                    help="thread the dedup IVF refits during the scale "
                         "build")
    args = ap.parse_args(argv)

    speed_pairs = 1500 if args.smoke else 4000
    speed_docs = 60 if args.smoke else 120
    scale_rows = args.rows or (4000 if args.smoke else 100_000)
    scale_docs = 60 if args.smoke else 500
    resume_rows = 200 if args.smoke else 800
    # keep the cache check meaningful at smoke scale: force the IVF tier
    flat_max = min(32768, max(64, scale_rows // 4))

    print(f"[1/4] speedup: {speed_pairs} pairs, wave {args.wave} ...")
    bench_speedup(200, args.wave)        # warm BLAS/allocators untimed
    speed = bench_speedup(speed_pairs, args.wave, n_docs=speed_docs)
    print(f"  sequential {speed['sequential']['pairs_per_sec']:8.0f} "
          f"pairs/s   batched {speed['batched']['pairs_per_sec']:8.0f} "
          f"pairs/s   speedup {speed['speedup']:.1f}x (floor 3x)")

    with tempfile.TemporaryDirectory() as td:
        td = Path(td)
        print(f"[2/4] scale build: {scale_rows} rows ...")
        store, scale = bench_scale(td / "scale", scale_rows, args.wave,
                                   scale_docs, args.background_recluster)
        print(f"  {scale['rows']} rows in {scale['seconds']:.1f}s "
              f"({scale['pairs_per_sec']:.0f} pairs/s, "
              f"{scale['discarded']} discarded, "
              f"dedup={scale['dedup_index_mode']}), "
              f"store {scale['store_mb']:.1f} MB")

        print("[3/4] index persistence: fit, persist, reload ...")
        cache = bench_index_cache(store, flat_max)
        store.close()
        print(f"  k-means fit {cache['build_seconds']:.2f}s -> cache load "
              f"{cache['load_seconds']:.2f}s "
              f"({cache['load_speedup']:.1f}x, {cache['n_lists']} lists)")

        print(f"[4/4] kill + resume identity: {resume_rows} rows ...")
        resume = bench_resume(td, resume_rows, 8)
        print(f"  resumed from row {resume['resumed_from']}; "
              f"{resume['files_compared']} files byte-identical: "
              f"{resume['identical']}")

    payload = {"speedup": speed, "scale": scale, "index_cache": cache,
               "resume": resume, "smoke": bool(args.smoke)}
    out_write("BENCH_precompute", payload, root_name="BENCH_precompute")

    ok = True
    if speed["speedup"] < 3.0:
        print("WARNING: batched pipeline below the 3x acceptance floor",
              file=sys.stderr)
        ok = False
    if not resume["identical"]:
        print("WARNING: resumed store differs from uninterrupted build",
              file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
