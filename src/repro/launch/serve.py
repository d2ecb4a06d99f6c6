"""Serving launcher: the StorInfer facade in front of any assigned arch.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
      --n-pairs 800 --n-queries 40

Opens (or builds, via the resumable batched pipeline) a precomputed store,
stands up the fallback engine for the chosen arch, and serves a query
stream two ways: the paper's sequential race (per-query hit rate +
latency), then the same stream through the staged serving pipeline
(``serve()``/``submit()``) reporting the decoupled hit/miss latency
percentiles and per-stage queue accounting. On real hardware pass
--no-smoke to load the full arch config instead of the reduced smoke one.
"""
import argparse
import tempfile

import numpy as np

from repro.api import EngineCfg, StorInfer, SystemCfg
from repro.core.kb import build_kb, sample_user_queries
from repro.core.tokenizer import Tokenizer
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    # BooleanOptionalAction: plain store_true with default=True made the
    # full-config mode unreachable (--smoke could never be turned off)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced arch config (--no-smoke loads the full "
                         "one)")
    ap.add_argument("--dataset", default="squad")
    ap.add_argument("--n-pairs", type=int, default=800)
    ap.add_argument("--n-queries", type=int, default=40)
    ap.add_argument("--s-th-run", type=float, default=0.9)
    ap.add_argument("--index", choices=("auto", "flat", "ivf"),
                    default="auto",
                    help="auto picks the tier from store size and loads a "
                         "persisted IVF fit from the store root if present")
    ap.add_argument("--store", default=None,
                    help="store dir (default: temp, rebuilt)")
    ap.add_argument("--decode-slots", type=int, default=4,
                    help="persistent continuous-batching decode slots for "
                         "the staged serving pipeline")
    args = ap.parse_args()

    kb = build_kb(args.dataset, n_docs=20)
    tok = Tokenizer.from_texts([d.text() for d in kb.docs], max_vocab=2048)
    cfg = SystemCfg(index=args.index, s_th_run=args.s_th_run,
                    decode_slots=args.decode_slots,
                    engine=EngineCfg(arch=args.arch, smoke=args.smoke,
                                     max_len=160, chunk=8))

    store_dir = args.store or tempfile.mkdtemp(prefix="storinfer_")
    try:
        si = StorInfer.open(store_dir, cfg, tokenizer=tok)
        print(f"loaded store: {si.store.count} pairs")
    except FileNotFoundError:
        si = StorInfer.build(kb, cfg, store_dir, n_pairs=args.n_pairs,
                             tokenizer=tok)
        st = si.build_stats
        print(f"built store: {si.store.count} pairs "
              f"({st.discarded} discarded), "
              f"{si.store.storage_bytes()['total_bytes'] / 1e6:.2f} MB")

    with si:
        user = sample_user_queries(kb, args.n_queries, seed=9)
        hits, lat = 0, []
        for q, _ in user:
            r = si.query(q, max_new=16)
            hits += r.hit
            lat.append(r.latency_s)
        print(f"sequential race: hit_rate={hits / len(user):.3f} "
              f"mean_latency={np.mean(lat):.3f}s p50={np.median(lat):.3f}s")

        # the same stream through the staged pipeline: hits resolve at
        # search time, misses on the continuous-batching decode loop
        with si.serve():
            futs = [si.submit(q, max_new=16) for q, _ in user]
            results = [f.result(timeout=600) for f in futs]
        hit_lat = [r.latency_s for r in results if r.hit]
        miss_lat = [r.latency_s for r in results if not r.hit]
        parts = []
        if hit_lat:
            parts.append(f"hit_p50={np.median(hit_lat) * 1e3:.1f}ms")
        if miss_lat:
            parts.append(f"miss_p50={np.median(miss_lat) * 1e3:.1f}ms")
        print(f"staged pipeline: {' '.join(parts) or 'no queries'}")
        snap = si.stats().pipeline
        if snap:
            depth = {k: v["items"] for k, v in snap["stages"].items()}
            print(f"  stage items: {depth}  "
                  f"search_batches={snap['search_batches']}")


if __name__ == "__main__":
    main()
