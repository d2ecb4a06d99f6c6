"""The program's spans and counters and the per-layer metrics that read
them: the readers on synthetic traces, the names they match against the
program's own constants, and a tiny serving pipeline traced on the CPU."""
from __future__ import annotations

import dataclasses
import types

import pytest

from conftest import REPO
from harness import spec, xtrace

DECODE_TILES = ("storinfer.decode.wait", "storinfer.decode.admit",
                "storinfer.decode.chunk", "storinfer.decode.finish")


def _ev(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=[])


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


CHUNK = "jit__decode_chunk_impl(1)"
SCAN = "jit_mips_topk_int8(3)"
PREFILL = "jit__prefill_impl(2)"


def span_trace(with_device=True):
    """Window [1000, 101000) ns. A search issued at 3000 while a decode
    chunk runs (2000-10000) waits for it: its scan runs at 10000. One at
    20000 finds the device free (scan at 20100). Searches at 30000 and
    30600 take the scans at 30200 and 31000 in turn. A wave's
    admission (40000-46000) keeps the device idle but for its prefill
    (42000-43000); a chunk span (50000-60000) around a decode chunk
    (50100-59000); a finish span (60000-60500) on an idle device; a wait
    span (61000-70000) that the host-idle share leaves out; a last chunk
    (70000-71000), after which the device trace holds nothing."""
    host = _plane("/host:CPU", [
        _line("pipeline-search", [
            _ev(xtrace.WINDOW_SPAN, 1000, 100000),
            _ev("storinfer.search.scan", 500, 600),      # starts before
            _ev("storinfer.search.scan", 3000, 8000),
            _ev("storinfer.search.scan", 20000, 1000),
            _ev("storinfer.search.scan", 30000, 500),
            _ev("storinfer.search.scan", 30600, 1400)]),
        _line("pipeline-decode", [
            _ev("storinfer.decode.admit", 40000, 6000),
            _ev("storinfer.decode.prefill", 41900, 1200),
            _ev("storinfer.decode.chunk", 50000, 10000),
            _ev("storinfer.decode.finish", 60000, 500),
            _ev("storinfer.decode.wait", 61000, 9000)])])
    runs = [(CHUNK, 2000, 8000), (SCAN, 10000, 500), (SCAN, 20100, 500),
            (SCAN, 30200, 200), (SCAN, 31000, 500), (PREFILL, 42000, 1000),
            (CHUNK, 50100, 8900), (CHUNK, 70000, 1000)]
    dev = _plane("/device:TPU:0", [
        _line("XLA Modules", [_ev(n, s, d) for n, s, d in runs]),
        _line("XLA Ops", [_ev(f"%op.{i} = f32[8]{{0}} fusion(f32[8] %p)",
                              s, d) for i, (_, s, d) in enumerate(runs)])])
    return xtrace.reduce_planes([host, dev] if with_device else [host])


def _read(name, **kw):
    base = dict(trace=None, snap0={}, snap1={})
    base.update(kw)
    return spec.load_reader(REPO, name).read(types.SimpleNamespace(**base))


def test_scan_queue_is_the_wait_for_the_device_behind_each_search():
    # queues: 7000 (behind the chunk), 100 (device free), 200, 400
    assert _read("scan_queue_ms.faq", trace=span_trace()) == \
        pytest.approx(1e-6 * (7000 + 100 + 200 + 400) / 4)
    mod = spec.load_reader(REPO, "scan_queue_ms.faq")
    got = mod.matches(span_trace())
    assert [m[2] for m in got] == [10000, 20100, 30200, 31000]
    # every matched scan ran inside its span: the clocks agree
    assert all(s0 <= p0 and p1 <= s1 for s0, s1, p0, p1 in got)


def test_scan_queue_keeps_each_search_with_its_own_scan():
    """A scan the device clock puts just before its span's start, and a
    span whose scan the trace lacks, leave every other match in place."""
    t = span_trace()
    dev = t.devices[0]
    dev.modules = [(n, s - 300, e - 300) if (s, e) == (20100, 20600)
                   else (n, s, e) for n, s, e in dev.modules
                   if (s, e) != (30200, 30400)]
    got = spec.load_reader(REPO, "scan_queue_ms.faq").matches(t)
    assert [(m[0], m[2]) for m in got] == [(3000, 10000), (20000, 19800),
                                           (30600, 31000)]
    assert _read("scan_queue_ms.faq", trace=t) == \
        pytest.approx(1e-6 * (7000 - 200 + 400) / 3)


def test_decode_host_idle_is_device_idle_under_admit_chunk_and_finish():
    # admit: 6000 less the 1000 ns prefill; chunk: 100 + 1000; finish 500;
    # over the traced stretch, from the window's start to the last op
    assert _read("decode_host_idle_share.novel", trace=span_trace()) == \
        pytest.approx(100.0 * (5000 + 1100 + 500) / (71000 - 1000))


def test_decode_host_idle_leaves_out_what_the_device_trace_missed():
    """A device trace that stops early (the profiler's buffer filled) is
    not read as idle: the finish span after the last op counts nothing."""
    t = span_trace()
    t.devices[0].ops = [o for o in t.devices[0].ops if o[2] < 60000]
    assert _read("decode_host_idle_share.novel", trace=t) == \
        pytest.approx(100.0 * (5000 + 100) / (59000 - 1000))


@pytest.mark.parametrize("name", ["scan_queue_ms.faq",
                                  "decode_host_idle_share.novel"])
def test_trace_readers_find_nothing_without_a_device_or_spans(name):
    """On the CPU the trace has no device plane; a program without the
    spans leaves the host with none: either way nothing to read."""
    assert _read(name) is None
    assert _read(name, trace=span_trace(with_device=False)) is None
    bare = span_trace()
    bare.host = [h for h in bare.host if not h[0].startswith("storinfer.")]
    assert _read(name, trace=bare) is None


def _slots(waves, admitted, **counters):
    return {"decode_slots": dict(waves=waves, admitted=admitted,
                                 **counters)}


def test_counter_readers_take_deltas_over_the_window():
    snap0 = _slots(4, 5, slot_wait_s=1.0, len_cuts=3)
    snap1 = _slots(14, 13, slot_wait_s=3.4, len_cuts=11)
    assert _read("slot_wait_ms.faq", snap0=snap0, snap1=snap1) == \
        pytest.approx(1e3 * 2.4 / 8)
    assert _read("wave_len_cut.novel", snap0=snap0, snap1=snap1) == \
        pytest.approx(100.0 * 8 / 10)


@pytest.mark.parametrize("name", ["slot_wait_ms.faq", "wave_len_cut.novel"])
def test_counter_readers_find_nothing_without_the_counters(name):
    """A program without the counters, or with no wave in the window."""
    assert _read(name, snap0=_slots(4, 5), snap1=_slots(9, 10)) is None
    idle = _slots(4, 5, slot_wait_s=1.0, len_cuts=3)
    assert _read(name, snap0=idle, snap1=idle) is None
    assert _read(name) is None


def test_reader_span_names_are_the_programs():
    from repro.core.index import SPAN_SCAN
    from repro.serving.engine import SPAN_ADMIT, SPAN_CHUNK, SPAN_FINISH
    from repro.serving.scheduler import SPAN_DECODE_WAIT
    scan = spec.load_reader(REPO, "scan_queue_ms.faq")
    idle = spec.load_reader(REPO, "decode_host_idle_share.novel")
    assert scan.SCAN_SPAN == SPAN_SCAN
    assert set(idle.DECODE_SPANS) == {SPAN_ADMIT, SPAN_CHUNK, SPAN_FINISH}
    assert set(DECODE_TILES) == set(idle.DECODE_SPANS) | {SPAN_DECODE_WAIT}


# -- the program traced: a tiny serving pipeline on the CPU ------------------

def _program_spans():
    from repro.core import index, runtime
    from repro.serving import engine, scheduler
    return {getattr(m, k) for m in (index, runtime, engine, scheduler)
            for k in dir(m) if k.startswith("SPAN_")}


def _tiny_runtime(tmp_path):
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config, reduced
    from repro.core.embedder import HashEmbedder
    from repro.core.kb import build_kb
    from repro.core.runtime import BatchedRuntime, BatchedRuntimeCfg
    from repro.core.store import PrecomputedStore
    from repro.core.tokenizer import Tokenizer
    from repro.models import model as M
    from repro.serving.engine import Engine
    kb = build_kb("squad", n_docs=4)
    tok = Tokenizer.from_texts([d.text() for d in kb.docs], max_vocab=512)
    cfg = dataclasses.replace(reduced(get_config("qwen3-1.7b")),
                              vocab_size=tok.vocab_size, n_layers=2)
    params = M.init_model(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    eng = Engine(cfg, params, tok, M.RunCfg(attn_impl="naive", remat=False),
                 max_len=96, chunk=4)
    emb = HashEmbedder()
    store = PrecomputedStore(tmp_path / "s", dim=emb.dim)
    qs = ["what is the height of aurora bridge?",
          "who founded the meridian institute?"]
    store.add_batch(emb.encode(qs), qs, ["two hundred meters.", "elena."])
    store.flush()
    rt = BatchedRuntime.from_store(
        store, emb, engine=eng,
        cfg=BatchedRuntimeCfg(max_wait_s=0.005, decode_slots=4,
                              add_misses=True, rebuild_every=1000))
    return rt, qs


def test_traced_pipeline_emits_every_span_and_its_decode_spans_tile(
        tmp_path):
    import jax
    rt, qs = _tiny_runtime(tmp_path)
    misses = ["a short novel zebra", "a much longer novel zebra prompt "
              "than the one before it", "another short novel yak",
              "one more zebra"]
    with rt:
        # compile outside the traced window
        rt.submit(misses[0], max_new=6).result(timeout=300)
        rt.submit(misses[1], max_new=6).result(timeout=300)
        with jax.profiler.trace(str(tmp_path / "trace")):
            with jax.profiler.TraceAnnotation(xtrace.WINDOW_SPAN):
                futs = [rt.submit(q, max_new=6) for q in misses + qs]
                res = [f.result(timeout=300) for f in futs]
                rt.stop_serving()
    assert [r.hit for r in res] == [False] * 4 + [True] * 2
    trace = xtrace.load(xtrace.find_xplane(tmp_path / "trace"))
    names = {h[0] for h in trace.host}
    want = _program_spans()
    assert len(want) == 11 and want <= names, want - names
    scan = spec.load_reader(REPO, "scan_queue_ms.faq")
    idle = spec.load_reader(REPO, "decode_host_idle_share.novel")
    assert {scan.SCAN_SPAN, *idle.DECODE_SPANS} <= names
    # the decode worker's four spans follow one another, never overlap,
    # and leave only the loop's glue between them
    tiles = sorted((s, e) for n, s, e in trace.host if n in DECODE_TILES)
    assert all(b[0] >= a[1] for a, b in zip(tiles, tiles[1:]))
    covered = sum(e - s for s, e in tiles)
    assert covered >= 0.95 * (tiles[-1][1] - tiles[0][0])
