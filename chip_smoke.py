#!/usr/bin/env python3
"""Smoke run of the StorInfer serving path on TPU, through the facade.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the sharded tier on a 4-chip host

One process; it refuses to run anywhere JAX finds no TPU. Phases, each of
which fails the run on its own:

  store   ``StorInfer.build`` on the squad KB (int8 shards), topped up with
          seeded filler rows to 150,016 rows at D=384 (the paper's 150K
          operating point, rounded up to whole 512-row scan tiles), plus a
          float16 copy of the same rows.
  search  64 user queries, engine off, through ``index="flat"`` on int8
          (Pallas int8 kernel), ``"flat"`` on fp16 (Pallas float kernel)
          and ``"auto"`` (IVF at this size). The flat tiers must run the
          ``"kernel"`` layout with compiled kernels and return the numpy
          references' top-1 rows.
  serve   ``StorInfer.open`` with qwen3-1.7b at full width (random weights
          from a seed): ``query()`` calls, then ``serve()``/``submit()``
          with hits and misses. Hits must return the stored pair; misses
          must decode at least one chunk of in-vocabulary token ids.
  lm      one prompt through ``Engine._prefill`` and 4 cached decode steps
          against ``M.forward`` at highest matmul precision.

``--four-chips`` runs only the store phase and the sharded tier over a
``model=4`` mesh, compared with the one-chip flat tier.

The last stdout line is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

N_ROWS = 150_016          # store rows: 293 scan tiles of 512
N_PAIRS = 3000            # KB pairs from the offline build
N_QUERIES = 64            # user queries per search tier
N_SERVE = 32              # queries submitted to the serving pipeline
MAX_NEW = 16              # decode budget per miss
MICROBATCH = 32           # search batch (the pipeline's max_batch)
SEED = 0
# max |engine logits - f32 reference| / max |reference|; the engine's
# matmuls take bf16 weights and activations (one MXU pass), the reference
# runs the same weights in f32 at "highest" (see CHANGES.md)
LOGIT_RTOL = 5e-2


def require(cond, msg):
    if not cond:
        raise RuntimeError(f"chip_smoke: {msg}")


def log(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


def device_gate(n_chips):
    """The device JAX reports, or exit before any phase runs."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {d.platform!r}; "
                 "no phase was run")
    if len(devs) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# store
# ---------------------------------------------------------------------------


def build_stores(root, n_rows=N_ROWS, n_pairs=N_PAIRS, fp16=True):
    """(kb, tokenizer, int8 store dir, fp16 store dir or None)."""
    import numpy as np

    from repro.api import StorInfer, SystemCfg
    from repro.core.kb import build_kb
    from repro.core.store import PrecomputedStore
    from repro.core.tokenizer import Tokenizer

    kb = build_kb("squad", seed=SEED)
    tok = Tokenizer.from_texts([d.text() for d in kb.docs])
    p8, p16 = root / "int8", root / "fp16"
    si = StorInfer.build(kb, SystemCfg(quantize=True, index="none"), p8,
                         n_pairs=n_pairs, tokenizer=tok, seed=SEED)
    n_kb = si.store.count
    kb_pairs = [si.store.get_pair(r) for r in range(n_kb)]
    kb_embs = si.embedder.encode([q for q, _ in kb_pairs])
    rng = np.random.default_rng(SEED)
    fill = rng.standard_normal((n_rows - n_kb, kb_embs.shape[1]),
                               dtype=np.float32)
    fill /= np.linalg.norm(fill, axis=1, keepdims=True)
    fq = [f"filler query {i}" for i in range(len(fill))]
    fr = [f"filler response {i}" for i in range(len(fill))]
    si.store.add_batch(fill, fq, fr)
    si.close()
    if not fp16:
        return kb, tok, p8, None
    with PrecomputedStore(p16, dim=kb_embs.shape[1],
                          emb_dtype="float16") as st:
        st.add_batch(kb_embs, [q for q, _ in kb_pairs],
                     [r for _, r in kb_pairs])
        st.add_batch(fill, fq, fr)
    log("store", f"{n_kb} KB pairs + {len(fill)} filler rows = {n_rows} "
                 f"rows, D={kb_embs.shape[1]}")
    return kb, tok, p8, p16


# ---------------------------------------------------------------------------
# search
# ---------------------------------------------------------------------------


def assert_compiled_kernel(fn, *args):
    """The scan must be a compiled Mosaic kernel, not interpret mode."""
    from repro.kernels import ops
    require(not ops._default_interpret(), "Pallas would run interpreted")
    hlo = fn.lower(*args).compile().as_text()
    require("tpu_custom_call" in hlo, "no tpu_custom_call in the scan")


def search_all(si, embs):
    import numpy as np
    vs, ids = [], []
    for lo in range(0, len(embs), MICROBATCH):
        v, i = si.index.search(embs[lo:lo + MICROBATCH], 1)
        vs.append(v[:, 0])
        ids.append(i[:, 0])
    return np.concatenate(vs), np.concatenate(ids)


def search_tier(name, path, index, queries, tok, *, mesh=None):
    """Open ``path`` on ``index`` (engine off), serve ``queries`` through
    ``query_batch`` and return (top-1 scores, rows, embeddings, si)."""
    from repro.api import StorInfer, SystemCfg, tier_of
    t0 = time.perf_counter()
    si = StorInfer.open(path, SystemCfg(index=index), tokenizer=tok,
                        mesh=mesh)
    t_open = time.perf_counter() - t0
    embs = si.embedder.encode(queries)
    t0 = time.perf_counter()
    v, i = search_all(si, embs)
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    v2, i2 = search_all(si, embs)
    t_warm = time.perf_counter() - t0
    require((i == i2).all() and (v == v2).all(),
            f"{name}: repeated search disagrees")
    hits = sum(r.hit for lo in range(0, len(queries), MICROBATCH)
               for r in si.query_batch(queries[lo:lo + MICROBATCH]))
    log("search", f"{name}: tier={tier_of(si.index)} open={t_open:.3f}s "
                  f"first_search={t_first:.3f}s (compile included) "
                  f"warm_search={t_warm:.4f}s hits={hits}/{len(queries)}")
    return v, i, embs, si


def check_int8_flat(si, embs, v, i):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.store import quantize_rows
    from repro.kernels import ops, ref
    dev = si.index.dev
    require(dev.layout == "kernel" and dev.quantized,
            f"int8 flat: layout {dev.layout!r}, quantized={dev.quantized}")
    q8, qs = quantize_rows(embs[:MICROBATCH])
    assert_compiled_kernel(ops.mips_topk_int8, jnp.asarray(q8),
                           jnp.asarray(qs), dev._x, dev._scales, 1)
    x8, xs = si.store.embeddings().take_q(np.arange(si.store.count))
    q8, qs = quantize_rows(embs)
    vr, ir = ref.mips_topk_int8_ref(q8, qs, x8, xs, 1)
    for j in np.flatnonzero(i != ir[:, 0])[:4]:
        acc = int(q8[j].astype(np.int32) @ x8[i[j]].astype(np.int32))
        log("search", f"int8 mismatch q{j}: kernel row {i[j]} score "
                      f"{v[j]!r} (ref score there {acc * qs[j] * xs[i[j]]!r})"
                      f"; ref row {ir[j, 0]} score {vr[j, 0]!r}")
    require((i == ir[:, 0]).all(),
            f"int8 flat: {(i != ir[:, 0]).sum()} top-1 rows differ "
            "from ref.mips_topk_int8_ref")
    exact = bool((v == vr[:, 0]).all())
    err = float(np.abs(v - vr[:, 0]).max())
    require(np.allclose(v, vr[:, 0], rtol=1e-6, atol=0),
            f"int8 flat: scores off the reference by {err}")
    log("search", f"int8 flat == ref.mips_topk_int8_ref: top-1 rows "
                  f"{len(i)}/{len(i)}, scores bit-exact={exact} "
                  f"(max abs diff {err:.3g})")


def check_fp16_flat(si, embs, v, i):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    dev = si.index.dev
    require(dev.layout == "kernel" and not dev.quantized,
            f"fp16 flat: layout {dev.layout!r}")
    require(dev._x.dtype == jnp.float32, f"resident dtype {dev._x.dtype}")
    assert_compiled_kernel(ops.mips_topk, jnp.asarray(embs[:MICROBATCH]),
                           dev._x, 1)
    x = np.asarray(si.store.embeddings()).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        vr, ir = ref.mips_topk_ref(jnp.asarray(embs), jnp.asarray(x), 2)
    vr, ir = np.asarray(vr), np.asarray(ir)
    # rows may differ only on a tie: equal top-1 and top-2 reference
    # scores, with the kernel's row scoring the same
    s_kernel = np.einsum("qd,qd->q", embs, x[i])
    tie = (vr[:, 0] - vr[:, 1] <= 1e-6) & \
        np.isclose(s_kernel, vr[:, 0], rtol=0, atol=1e-6)
    diff = i != ir[:, 0]
    require(not (diff & ~tie).any(),
            f"fp16 flat: {(diff & ~tie).sum()} top-1 rows differ from "
            "ref.mips_topk_ref")
    err = float(np.abs(v - vr[:, 0]).max())
    require(err <= 1e-5, f"fp16 flat: scores off the reference by {err}")
    log("search", f"fp16 flat == ref.mips_topk_ref: top-1 rows "
                  f"{int((~diff).sum())}/{len(i)} equal, {int(diff.sum())} "
                  f"on exact ties; max abs score diff {err:.3g}")


def search_phase(kb, tok, p8, p16):
    from repro.core.index import FlatIndex, IVFIndex
    from repro.core.kb import sample_user_queries
    queries = [q for q, _ in sample_user_queries(kb, N_QUERIES, seed=1)]

    v8, i8, embs, si = search_tier("flat/int8", p8, "flat", queries, tok)
    require(isinstance(si.index, FlatIndex), "flat/int8 is not FlatIndex")
    check_int8_flat(si, embs, v8, i8)
    si.close()

    v, i, _, si = search_tier("flat/fp16", p16, "flat", queries, tok)
    require(isinstance(si.index, FlatIndex), "flat/fp16 is not FlatIndex")
    check_fp16_flat(si, embs, v, i)
    si.close()

    _, iv, _, si = search_tier("auto/int8", p8, "auto", queries, tok)
    require(isinstance(si.index, IVFIndex), "auto at 150K is not IVF")
    log("search", f"IVF recall@1 vs flat int8: {(iv == i8).mean():.4f} "
                  f"(n_lists={si.index.n_lists}, nprobe={si.index.nprobe})")
    si.close()
    del si
    gc.collect()
    return queries


# ---------------------------------------------------------------------------
# serve + lm
# ---------------------------------------------------------------------------


def check_result(si, tok, vocab, q, r, row):
    if r.hit:
        require((r.matched_query, r.response) == si.store.get_pair(row),
                f"hit {q!r}: response is not the stored pair {row}")
        return
    ids = r.token_ids
    require(r.chunks_run >= 1, f"miss {q!r}: no decode chunk ran")
    require(1 <= len(ids) <= MAX_NEW, f"miss {q!r}: {len(ids)} tokens")
    require(all(0 <= t < vocab for t in ids), f"miss {q!r}: ids {ids}")
    require(r.response == tok.decode(ids), f"miss {q!r}: text != ids")


def serve_phase(tok, p8, queries, ecfg):
    import numpy as np

    from repro.api import StorInfer, SystemCfg
    from repro.core.runtime import BatchedRuntimeCfg
    cfg = SystemCfg(index="flat", engine=ecfg, decode_slots=4,
                    batched=BatchedRuntimeCfg(add_misses=True))
    t0 = time.perf_counter()
    si = StorInfer.open(p8, cfg, tokenizer=tok)
    log("serve", f"open with {ecfg.arch} (smoke={ecfg.smoke}): "
                 f"{time.perf_counter() - t0:.1f}s")
    require(si.index.dev.layout == "kernel", "serve: not the kernel layout")
    vocab = si.engine.cfg.padded_vocab
    serve_q = queries[:N_SERVE]
    _, rows = search_all(si, si.embedder.encode(serve_q))

    t0 = time.perf_counter()
    for q, row in zip(serve_q[:3], rows[:3]):
        r = si.query(q, max_new=MAX_NEW)
        check_result(si, tok, vocab, q, r, row)
        log("serve", f"query(): hit={r.hit} score={r.score:.4f} "
                     f"chunks={r.chunks_run} latency={r.latency_s:.3f}s")
    log("serve", f"3 query() calls: {time.perf_counter() - t0:.1f}s "
                 "(compiles included)")

    t0 = time.perf_counter()
    with si.serve():
        futs = [si.submit(q, max_new=MAX_NEW) for q in serve_q]
        results = [f.result(timeout=600) for f in futs]
    t_serve = time.perf_counter() - t0
    for q, r, row in zip(serve_q, results, rows):
        check_result(si, tok, vocab, q, r, row)
    n_hit = sum(r.hit for r in results)
    n_miss = len(results) - n_hit
    require(n_hit > 0 and n_miss > 0, f"serve: {n_hit} hits {n_miss} misses")
    snap = si.stats().pipeline
    require(snap["writeback_errors"] == 0,
            f"serve: {snap['writeback_errors']} write-back errors")
    hit_ms = [r.latency_s * 1e3 for r in results if r.hit]
    miss_ms = [r.latency_s * 1e3 for r in results if not r.hit]
    log("serve", f"serve(): {len(results)} futures resolved in "
                 f"{t_serve:.1f}s (compiles included): hits={n_hit} "
                 f"misses={n_miss} hit_p50={np.median(hit_ms):.1f}ms "
                 f"miss_p50={np.median(miss_ms):.1f}ms "
                 f"writebacks={si.stats().runtime.writebacks} "
                 f"writeback_errors=0 slots={snap.get('decode_slots')}")
    return si


def lm_phase(engine, prompt, n_steps=4):
    """Prefill + ``n_steps`` cached decode steps vs the full-sequence f32
    forward at highest precision of the same weights; returns the max
    relative error."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M
    e = engine
    ids = e.tok.encode(prompt, bos=True)
    logits, cache = e._prefill(e.params, jnp.asarray([ids], jnp.int32))
    got = [np.asarray(logits[0, -1], np.float32)]
    step = jax.jit(lambda p, t, c, n: M.decode_step(e.cfg, p, t, c, n,
                                                     e.run))
    seq = list(ids)
    for s in range(n_steps):
        nxt = int(np.argmax(got[-1]))
        seq.append(nxt)
        logits, cache, _ = step(e.params, jnp.asarray([[nxt]], jnp.int32),
                                cache, jnp.asarray(len(ids) + s, jnp.int32))
        got.append(np.asarray(logits[0, -1], np.float32))
    f32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), e.params)
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, t: M.forward(e.cfg, p, {"tokens": t},
                                             M.RunCfg(attn_impl="naive",
                                                      remat=False))[0])
        want = np.asarray(fwd(f32, jnp.asarray([seq], jnp.int32))[0],
                          np.float32)[len(ids) - 1:]
    # padded vocab columns hold a -1e30 mask on both sides
    V = e.cfg.vocab_size
    got, want = np.stack(got)[:, :V], want[:, :V]
    rel = float(np.abs(got - want).max() / np.abs(want).max())
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    log("lm", f"prompt {len(ids)} tokens + {n_steps} cached decode steps vs "
              f"M.forward(highest): max rel err {rel:.3e} (tolerance "
              f"{LOGIT_RTOL:g}); greedy token agreement {agree}/{len(got)}")
    require(np.isfinite(got).all(), "lm: non-finite logits")
    require(rel <= LOGIT_RTOL, f"lm: logit error {rel} > {LOGIT_RTOL}")
    return rel


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def four_chip_phase(kb, tok, p8):
    import numpy as np

    from repro.core.index import ShardedIndex
    from repro.core.kb import sample_user_queries
    from repro.launch.mesh import make_local_mesh
    queries = [q for q, _ in sample_user_queries(kb, N_QUERIES, seed=1)]
    vf, i_f, _, si = search_tier("flat/int8 (1 chip)", p8, "flat", queries,
                                 tok)
    si.close()
    mesh = make_local_mesh(model=4)
    vs, i_s, _, si = search_tier("auto/int8 (model=4 mesh)", p8, "auto",
                                 queries, tok, mesh=mesh)
    require(isinstance(si.index, ShardedIndex), "auto on 4 chips: not "
            "the sharded tier")
    shards = si.index.embs.addressable_shards
    rows = sorted(s.data.shape[0] for s in shards)
    devs = {s.device.id for s in shards}
    require(len(devs) == 4 and rows[-1] - rows[0] <= 1
            and sum(rows) >= si.store.count,
            f"sharded rows per device {rows} on devices {sorted(devs)}")
    same = int((i_s == i_f).sum())
    log("four", f"rows per device {rows} (store {si.store.count}); top-1 "
                f"rows equal to the 1-chip flat tier {same}/{len(i_f)}; "
                f"scores bit-equal={bool((vs == vf).all())}")
    require(same == len(i_f), "sharded top-1 differs from flat")
    require(np.allclose(vs, vf, rtol=1e-6, atol=0), "sharded scores differ")
    si.close()


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded tier over four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: {SRC / 'repro'} not found; run from a "
                 "checkout of the repo")
    device = device_gate(4 if args.four_chips else 1)
    sys.path.insert(0, str(SRC))
    from repro.api import EngineCfg
    from repro.launch.compile_cache import enable_compile_cache
    print(f"[device] compile cache: {enable_compile_cache()}", flush=True)

    t_all = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as td:
        t0 = time.perf_counter()
        kb, tok, p8, p16 = build_stores(Path(td),
                                        fp16=not args.four_chips)
        log("store", f"phase seconds {time.perf_counter() - t0:.1f}")
        if args.four_chips:
            t0 = time.perf_counter()
            four_chip_phase(kb, tok, p8)
            log("four", f"phase seconds {time.perf_counter() - t0:.1f}")
        else:
            t0 = time.perf_counter()
            queries = search_phase(kb, tok, p8, p16)
            log("search", f"phase seconds {time.perf_counter() - t0:.1f}")
            t0 = time.perf_counter()
            si = serve_phase(tok, p8, queries,
                             EngineCfg(arch="qwen3-1.7b", smoke=False))
            log("serve", f"phase seconds {time.perf_counter() - t0:.1f}")
            t0 = time.perf_counter()
            lm_phase(si.engine, queries[0])
            log("lm", f"phase seconds {time.perf_counter() - t0:.1f}")
            si.close()
    print(f"[done] all phases passed in {time.perf_counter() - t_all:.1f}s",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
