#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration, its traffic mix and its metrics come from
``BENCHMARK.json`` and the files it names (see ``harness/spec.py``). One
run: build the store on the checkout's first run (the offline precompute,
timed apart from ``setup_s``) or reuse it, open the system through
``StorInfer.open``, warm up every shape the cell's traffic uses (set-up),
drive the traffic through ``StorInfer.submit`` for ``--seconds``, wait for
what is due, then free the program and judge every route and a sample of
the answers against the plain reference (``harness/check.py``).

The last stdout line is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device`` (``--trace 0``: the cell's
end-to-end metrics; ``--trace 1``: its per-layer metrics, with ``busy_s``,
``window_s`` and ``breakdown``), and last ``checks``: each number
compared with its limit. The same numbers close standard error. Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from harness import spec  # noqa: E402

TRACE_DIR = Path("experiments") / "bench_trace"
COMPILE_CACHE = Path("experiments") / "bench_jax_cache"
WARM_HITS = 8


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def device_gate(chips: int) -> dict:
    """The device JAX reports; exits before any work without a TPU or with
    fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    d = devs[0]
    log(f"device platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        sys.exit(f"bench: needs a TPU, JAX found {d.platform!r}; no result")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}; no result")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class CompileCounter:
    """Counts, while armed, the programs lowered (every new shape costs
    one), how many of them the persistent cache held and how many had to
    be compiled."""

    def __init__(self):
        import jax
        self.armed = False
        self.reset()
        self._dur, self._ev = self._on_duration, self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def reset(self):
        self.lowered = self.requests = self.hits = 0

    @property
    def compiled(self) -> int:
        return self.requests - self.hits

    def _on_duration(self, name, _secs, **_kw):
        if self.armed and name.endswith("jaxpr_to_mlir_module_duration"):
            self.lowered += 1

    def _on_event(self, name, **_kw):
        if not self.armed:
            return
        if name.endswith("compilation_cache/compile_requests_use_cache"):
            self.requests += 1
        elif name.endswith("compilation_cache/cache_hits"):
            self.hits += 1

    def close(self):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._ev)


def build_traffic(cell, seed, seconds, sut):
    """The run's requests and, in the open loop, their send times."""
    from harness import reference as R
    from harness import traffic as T
    mix, th = cell["mix"], cell["cfg"]["serving"]["s_th_run"]

    def is_hit(texts):
        return sut.ref_kb.best(texts)[0] >= th

    def token_len(text):
        return len(R.encode(text, sut.vocab))

    sched = T.schedule(mix, T.n_requests(mix, seconds), sut.users, is_hit,
                       token_len, sut.answer_lens)
    reqs = T.plan(sched, seed, sut.users, is_hit, token_len)
    times = (T.arrivals(mix, sched, seconds) if mix["loop"] == "open"
             else None)
    return reqs, times


def warm_up(si, cell, reqs, stored_queries):
    """Compile every shape the cell's traffic uses: the MIPS scan at each
    microbatch size, and through ``submit`` one of the plan's misses at
    each prompt length it holds (prefill, slot write, decode chunk at the
    cell's slot count) and a few of its hits. Returns the seconds it
    took."""
    t0 = time.perf_counter()
    mb = si.cfg.batched.max_batch
    embs = si.embedder.encode(stored_queries[:mb])
    for q in range(1, mb + 1):
        si.index.search(embs[:q], 1)
    chunk = cell["cfg"]["serving"]["chunk"]
    by_len = {r.prompt_len: r.text for r in reqs if r.kind == "miss"}
    futs = [si.submit(text, max_new=2 * chunk + 1)
            for _, text in sorted(by_len.items())]
    futs += [si.submit(r.text) for r in
             [r for r in reqs if r.kind == "hit"][:WARM_HITS]]
    for f in futs:
        f.result(timeout=1200)
    return time.perf_counter() - t0


def percentile(values, q):
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


def end_to_end(metrics, recs, t0, t_end, deadline, setup_s):
    """The cell's end-to-end metrics over every request due in the window.
    A failed or unanswered request counts at the drain deadline."""
    from harness.drive import latency_ms, served_kind
    out = {}
    seconds = t_end - t0
    for m in metrics:
        kind = spec.e2e_kind(m["name"])
        if kind[0] == "pct":
            sel = [latency_ms(r, deadline) for r in recs
                   if kind[1] in ("all", served_kind(r))]
            if not sel:
                continue
            v = percentile(sel, kind[2])
        elif kind[0] == "output_tokens_per_s":
            v = sum(len(r.result.token_ids) for r in recs
                    if r.done and r.error is None and not r.result.hit
                    and t0 <= r.done < t_end) / seconds
        else:                                   # setup_s
            v = setup_s
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def per_layer(root, metrics, ctx):
    out = {}
    for m in metrics:
        v = spec.load_reader(root, m["name"]).read(ctx)
        if v is None:
            log(f"per-layer {m['name']}: nothing to read in this run")
            continue
        out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def knowledge_view(cfg: dict):
    """What the benchmark itself knows of a configuration, with no device
    and no program running: the users model, the stored pairs, the
    reference's vocabulary and its scan of the stored pairs, the answer
    lengths the schedule draws budgets from, and the program's tokenizer
    (``tok``) for the store's build."""
    from harness import check as C
    from harness import reference as R
    from harness import system
    facts, users, pairs, texts, tok = system.knowledge(cfg)
    vocab = R.vocab_ids(texts)
    return types.SimpleNamespace(
        users=users, pairs=pairs, vocab=vocab, tok=tok,
        answer_lens=[len(R.encode(f.answer(), vocab)) - 1 for f in facts],
        ref_kb=C.RefStore(cfg["store"], pairs, None))


def open_cell(root: Path, cell: dict, seed: int, require_chip: bool = True):
    """Set-up up to serving: the configuration's architecture module
    (``arch``, found before anything else opens), the device, the compile
    cache, the store, the system opened with weights from ``seed`` once
    ``arch`` has checked its widths, and the reference's own stored pairs,
    vocabulary and answer lengths. ``store_s`` is the time the store's
    one-off build took (0 when the checkout has it)."""
    from harness import system
    arch = spec.load_arch(root, cell["cfg"])
    system.program_on_path(root)
    import jax
    device = (device_gate(cell["chips"]) if require_chip else
              {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind,
               "count": len(jax.devices())})
    # the compile cache lives at one fixed path inside the checkout, whatever
    # the environment names, so two checkouts share nothing
    cache = str(root / COMPILE_CACHE)
    jax.config.update("jax_compilation_cache_dir", cache)
    # every program the run makes, however quick to compile, is kept, so a
    # cell's later runs read all of them from the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache {cache}")
    cfg = cell["cfg"]
    weights_seed = seed % (2 ** 31 - 1)
    sut = knowledge_view(cfg)
    t = time.perf_counter()
    store = system.ensure_store(root, cfg, sut.pairs, sut.tok, log)
    sut.store_s = time.perf_counter() - t
    sut.si = system.open_system(root, cfg, arch, store, sut.tok,
                                weights_seed)
    sut.device, sut.weights_seed, sut.arch = device, weights_seed, arch
    return sut


def run(argv=None, *, root: Path = None, require_chip: bool = True,
        control: bool = False, hook=None) -> dict:
    """One run of a cell. ``root`` is the checkout (the parent of
    ``bench/`` by default). ``require_chip=False`` and ``hook`` exist for
    the harness's own tests: they skip the look for a TPU and let a test
    break the path under test (``hook(si)`` after set-up). With
    ``control`` the float8 control's tokens stand in for the served ones
    in the comparison, so ``correct`` is the control's verdict; the
    program's own reading is kept as ``program_logit_gap``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    t_begin = time.perf_counter()
    root = Path(root or HERE.parent)
    bench = spec.load_benchmark(root)
    cell = spec.load_cell(root, bench, args.workload)
    cfg, mix = cell["cfg"], cell["mix"]
    seed = args.seed
    sut = open_cell(root, cell, seed, require_chip)
    si, device, arch = sut.si, sut.device, sut.arch

    import jax

    from harness import check as C
    from harness import drive, system, xtrace
    vocab, pairs = sut.vocab, sut.pairs
    reqs, times = build_traffic(cell, seed, args.seconds, sut)
    if hook is not None:
        hook(si)
    counter = CompileCounter()

    with si.serve():
        counter.armed = True
        warm_s = warm_up(si, cell, reqs, [q for q, _ in pairs])
        # the store's one-off build is the offline precompute, not serving
        # set-up: a checkout's first run makes it, later runs open it
        setup_s = time.perf_counter() - t_begin - sut.store_s
        log(f"set-up {setup_s:.3f}s, store build {sut.store_s:.3f}s "
            f"(warm-up {warm_s:.3f}s: "
            f"{counter.lowered} programs lowered, {counter.hits} read from "
            f"the compile cache, {counter.compiled} compiled); "
            f"{len(reqs)} requests planned")
        counter.reset()
        snap0 = si.stats().pipeline
        trace_dir = None
        if args.trace:
            (root / TRACE_DIR).mkdir(parents=True, exist_ok=True)
            trace_dir = Path(tempfile.mkdtemp(prefix="trace_",
                                              dir=root / TRACE_DIR))
            jax.profiler.start_trace(
                str(trace_dir), profiler_options=_profile_options())
        counter.armed = True
        with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
            t0 = time.perf_counter()
            t_end = t0 + args.seconds
            if mix["loop"] == "open":
                recs = drive.open_loop(si, reqs, times, t0)
                time.sleep(max(0.0, t_end - time.perf_counter()))
            else:
                recs = drive.closed_loop(si, reqs, mix["clients"], t_end)
        counter.armed = False
        counter.close()
        if args.trace:
            jax.profiler.stop_trace()
        snap1 = si.stats().pipeline
        deadline = t_end + mix["drain_s"]
        still = drive.drain(recs, deadline)
        try:
            peak = max(d.memory_stats()["peak_bytes_in_use"]
                       for d in jax.local_devices())
        except (TypeError, KeyError):
            peak = None
    log(f"window {args.seconds}s: {len(recs)} sent, {still} unanswered at "
        f"the drain limit; in the window {counter.lowered} programs "
        f"lowered, {counter.compiled} compiled")
    if times is not None:
        late = [(r.sent - r.due) * 1e3 for r in recs]
        log(f"generator lateness ms: p50 {percentile(late, 50):.3f} "
            f"p99 {percentile(late, 99):.3f} max {max(late):.3f}")

    attempted = len(recs)
    failed = sum(1 for r in recs if not r.done or r.error is not None)
    for r in recs:
        if r.error:
            log(f"failed request: {r.error}")
            break
    ctx = types.SimpleNamespace(
        cfg=cfg, arch=arch, mix=mix, cell=cell, recs=recs, t0=t0,
        t_end=t_end, window_s=args.seconds, deadline=deadline, snap0=snap0,
        snap1=snap1, trace=None, peaks=None, store_rows=si.store.count)
    if args.trace:
        ctx.peaks = spec.load_peaks(root, device["kind"]) \
            if require_chip else None
        t = time.perf_counter()
        ctx.trace = xtrace.load(xtrace.find_xplane(trace_dir))
        shutil.rmtree(trace_dir)
        log(f"trace read in {time.perf_counter() - t:.1f}s: "
            f"{sum(len(d.ops) for d in ctx.trace.devices)} device ops")
        metrics = per_layer(root, spec.per_layer_for(bench, cell["name"]),
                            ctx)
        device["busy_s"] = ctx.trace.busy_s()
        device["window_s"] = ctx.trace.window_s
    else:
        metrics = end_to_end(spec.end_to_end_for(bench, cell["name"]), recs,
                             t0, t_end, deadline, setup_s)
    device["memory_peak_bytes"] = peak

    # free the program, and on the chip every device buffer it left, so the
    # reference has the chip's memory
    si.close()
    del si
    sut.si = None
    gc.collect()
    if require_chip:
        for a in jax.live_arrays():
            a.delete()
    t = time.perf_counter()
    filler = system.filler_rows(cfg["store"], len(pairs))
    ref_store = C.RefStore(cfg["store"], pairs, filler)
    checks = C.judge(cfg, arch, mix, seed, recs, ref_store, vocab,
                     sut.weights_seed, control=control)
    log(f"reference check in {time.perf_counter() - t:.1f}s: "
        f"{checks['routes_checked']} routes, {checks['hits_checked']} hits, "
        f"{checks['misses_checked']} misses, "
        f"{checks.get('tokens_checked', 0)} served tokens")
    # an answer that never came is as wrong as a wrong one
    result = {"correct": C.correct(checks, mix) and failed == 0,
              "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": ctx.trace.top_ops(10),
                               "idle_gaps": ctx.trace.idle_gaps(10)}
    result["compiles_in_window"] = counter.lowered
    if control:
        result["program_logit_gap"] = checks.get("program_logit_gap")
    result["checks"] = {k: v for k, v in checks.items()
                        if isinstance(v, dict)}
    return result


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def report(result: dict):
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    try:
        res = run()
    except spec.SpecError as e:
        sys.exit(f"bench: {e}")
    except FileNotFoundError as e:
        sys.exit(f"bench: {e}")
    if not all(math.isfinite(m["value"]) for m in res["metrics"].values()):
        sys.exit("bench: a metric is not finite; no result")
    report(res)
