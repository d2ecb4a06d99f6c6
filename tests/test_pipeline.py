"""Staged serving pipeline: hit-latency decoupling (hit futures resolve
at MIPS-search time, never gated by miss decode), persistent decode-slot
reuse across admissions, background write-back + atomic index swap,
per-request latency stamping, and the MicroBatcher submit-after-stop
window."""
import dataclasses
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.embedder import HashEmbedder
from repro.core.index import FlatIndex
from repro.core.kb import build_kb
from repro.core.runtime import (BatchedRuntime, BatchedRuntimeCfg,
                                RuntimeCfg, StorInferRuntime)
from repro.core.store import PrecomputedStore
from repro.core.tokenizer import Tokenizer
from repro.models import model as M
from repro.serving.engine import Engine
from repro.serving.scheduler import MicroBatcher


@pytest.fixture(scope="module")
def engine_parts():
    """Arch config + params + tokenizer; each test builds its own Engine
    (cheap — params are shared, jit caches are per-instance) so decode
    can be slowed per-test without leaking into the shared fixture."""
    kb = build_kb("squad", n_docs=4)
    tok = Tokenizer.from_texts([d.text() for d in kb.docs], max_vocab=512)
    cfg = dataclasses.replace(
        reduced(get_config("qwen3-1.7b")),
        vocab_size=tok.vocab_size, n_layers=2)
    params = M.init_model(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    run = M.RunCfg(attn_impl="naive", remat=False)
    return cfg, params, tok, run


def make_engine(parts, decode_delay_s: float = 0.0) -> Engine:
    """A fresh Engine; ``decode_delay_s`` turns it into the slow-decode
    stub — every decode chunk sleeps first, so miss latency is reliably
    dominated by decode while hits stay search-speed."""
    cfg, params, tok, run = parts
    eng = Engine(cfg, params, tok, run, max_len=96, chunk=4)
    if decode_delay_s > 0:
        orig = eng._decode_chunk

        def slowed(*a, **kw):
            time.sleep(decode_delay_s)
            return orig(*a, **kw)

        eng._decode_chunk = slowed
    return eng


@pytest.fixture()
def stored(tmp_path):
    emb = HashEmbedder()
    store = PrecomputedStore(tmp_path / "s", dim=emb.dim)
    qs = ["what is the height of aurora bridge?",
          "who founded the meridian institute?",
          "when was the treaty of helsport signed?"]
    rs = ["the height is two hundred meters.",
          "elena marchetti founded it.",
          "it was signed in 1907."]
    store.add_batch(emb.encode(qs), qs, rs)
    store.flush()
    return emb, store, qs, rs


def _resolve_times(futs, timeout=300):
    """Wait for every future and return its wall-clock resolve stamp."""
    stamps = {}
    lock = threading.Lock()

    def stamp(i):
        def cb(_):
            with lock:
                stamps[i] = time.perf_counter()
        return cb

    for i, f in enumerate(futs):
        f.add_done_callback(stamp(i))
    for f in futs:
        f.result(timeout=timeout)
    return [stamps[i] for i in range(len(futs))]


# ---------------------------------------------------------------------------
# hit-latency decoupling
# ---------------------------------------------------------------------------


def test_hit_futures_resolve_before_any_miss(engine_parts, stored):
    """The tentpole contract: with decode made slow, every hit future —
    even ones submitted AFTER the misses — resolves before any miss
    future, because hits return at MIPS-search time."""
    emb, store, qs, rs = stored
    eng = make_engine(engine_parts, decode_delay_s=0.05)
    with BatchedRuntime.from_store(
            store, emb, engine=eng,
            cfg=BatchedRuntimeCfg(max_wait_s=0.005, decode_slots=2)) as rt:
        miss_futs = [rt.submit(f"novel zebra question number {i}",
                               max_new=8) for i in range(3)]
        time.sleep(0.15)                  # decode is underway
        hit_futs = [rt.submit(q, max_new=8) for q in qs]
        hit_t = _resolve_times(hit_futs)
        miss_t = _resolve_times(miss_futs)
        hit_res = [f.result() for f in hit_futs]
        miss_res = [f.result() for f in miss_futs]

        assert max(hit_t) < min(miss_t), \
            "a hit future waited on a miss decode"
        assert [r.response for r in hit_res] == rs
        assert all(r.hit and r.source == "store" and r.llm_s == 0.0
                   for r in hit_res)
        assert all((not r.hit) and r.source == "llm" and r.response
                   for r in miss_res)
        # per-submission stamps: each miss carries its own latency, and
        # miss latency dominates hit latency
        assert max(r.latency_s for r in hit_res) \
            < min(r.latency_s for r in miss_res)

        hit_ms = [r.latency_s * 1e3 for r in hit_res]
        miss_ms = [r.latency_s * 1e3 for r in miss_res]
        assert len(hit_ms) == 3 and len(miss_ms) == 3
        assert np.median(hit_ms) < np.median(miss_ms)
        snap = rt.pipeline_stats()
        assert snap["stages"]["search"]["items"] == 6
        assert snap["stages"]["decode"]["items"] == 3
    assert rt.stats.queries == 6
    assert rt.stats.hits == 3 and rt.stats.misses == 3


def test_decode_slots_reused_across_admissions(engine_parts, stored):
    """Misses beyond the slot count refill freed slots on ONE persistent
    scheduler (no per-batch teardown): more admissions than slots, spread
    over multiple waves, through the same BatchScheduler instance."""
    emb, store, qs, rs = stored
    eng = make_engine(engine_parts)
    with BatchedRuntime.from_store(
            store, emb, engine=eng,
            cfg=BatchedRuntimeCfg(max_wait_s=0.005, decode_slots=2)) as rt:
        pipeline = rt.serve()
        futs = [rt.submit(f"unseen xylophone query variant {i}", max_new=6)
                for i in range(5)]
        res = [f.result(timeout=300) for f in futs]
        assert all(not r.hit and r.response for r in res)
        sched = pipeline.scheduler
        assert sched is rt.serve().scheduler      # one persistent loop
        assert sched.B == 2
        assert sched.admitted == 5                # > slot count
        assert sched.waves >= 2                   # refilled between waves
        assert max(sched.slot_uses) >= 2          # an actual slot reused
        assert sum(sched.slot_uses) == 5


def test_background_rebuild_swaps_index_without_dropping(engine_parts,
                                                         stored):
    """§3.1 write-back + flush_and_rebuild run off the critical path; the
    index swap is atomic — queries in flight during the rebuild resolve
    exactly once with correct responses, and the written-back pair serves
    as a hit afterwards."""
    emb, store, qs, rs = stored
    eng = make_engine(engine_parts)
    with BatchedRuntime.from_store(
            store, emb, engine=eng,
            cfg=BatchedRuntimeCfg(max_wait_s=0.005, decode_slots=2,
                                  add_misses=True, rebuild_every=1,
                                  async_writeback=True)) as rt:
        novel = "a brand new zebra question never stored before"
        first = rt.submit(novel, max_new=8).result(timeout=300)
        assert not first.hit and first.response
        # hits submitted while the background rebuild races along
        during = [rt.submit(qs[i % 3], max_new=8) for i in range(6)]
        deadline = time.monotonic() + 60
        while rt.stats.index_rebuilds < 1:
            assert time.monotonic() < deadline, "rebuild never happened"
            time.sleep(0.02)
        res = [f.result(timeout=300) for f in during]
        assert [r.response for r in res] == [rs[i % 3] for i in range(6)]
        assert all(r.hit for r in res)
        # the grown store now serves the written-back pair as a hit
        again = rt.submit(novel, max_new=8).result(timeout=300)
        assert again.hit and again.response == first.response
        assert store.count == 4
        assert rt.stats.writebacks == 1


def test_pipeline_without_engine_resolves_misses_empty(stored):
    emb, store, qs, rs = stored
    with BatchedRuntime.from_store(
            store, emb, cfg=BatchedRuntimeCfg(max_wait_s=0.01)) as rt:
        futs = [rt.submit(q) for q in qs + ["novel zebra"]]
        res = [f.result(timeout=60) for f in futs]
        assert [r.hit for r in res] == [True, True, True, False]
        assert res[3].source == "llm" and res[3].response == ""
        snap = rt.pipeline_stats()
        # engine-less misses resolve through the hit-resolve stage
        assert snap["stages"]["resolve"]["items"] == 4
        assert snap["stages"]["decode"]["items"] == 0
        assert set(snap["stages"]) == {"search", "resolve", "decode",
                                       "writeback"}
    assert rt.stats.queries == 4 and rt.stats.hits == 3


def test_pipeline_rejects_bad_knobs(stored):
    emb, store, qs, rs = stored
    with BatchedRuntime.from_store(
            store, emb, cfg=BatchedRuntimeCfg(queue_depth=0)) as rt:
        with pytest.raises(ValueError):
            rt.serve()
    with BatchedRuntime.from_store(
            store, emb, cfg=BatchedRuntimeCfg(decode_slots=0)) as rt:
        with pytest.raises(ValueError):
            rt.serve()


def test_pipeline_submit_after_stop_raises_then_restarts(stored):
    emb, store, qs, rs = stored
    with BatchedRuntime.from_store(store, emb) as rt:
        p = rt.serve()
        assert rt.submit(qs[0]).result(timeout=60).hit
        rt.stop_serving()
        with pytest.raises(RuntimeError, match="not running"):
            p.submit("too late")
        # the runtime stays usable: serve() starts a fresh pipeline
        assert rt.submit(qs[1]).result(timeout=60).hit
        assert rt.serve() is not p


def test_batch_scheduler_temperature_gates_waves(engine_parts):
    """Decode runs one temperature per chunk, so a wave must admit only
    same-temperature requests — a mixed pair forms two waves instead of
    silently decoding with the first slot's temperature."""
    from repro.serving.engine import BatchScheduler, Request
    eng = make_engine(engine_parts)
    sched = BatchScheduler(eng, batch_size=4)
    sched.submit(Request(rid=0, prompt="same length prompt a", max_new=4))
    sched.submit(Request(rid=1, prompt="same length prompt b", max_new=4,
                         temperature=1.0))
    sched._admit()
    assert int(sched.live.sum()) == 1    # greedy wave first, sampled waits
    done = sched.run_to_completion()
    assert len(done) == 2 and sched.waves == 2


def test_batch_scheduler_counts_waves_cut_on_prompt_length(engine_parts):
    """A wave closes at the first waiting request whose prompt length
    differs while a slot is still free (``len_cuts``); equal lengths fill
    one wave and cut nothing."""
    from repro.serving.engine import BatchScheduler, Request
    eng = make_engine(engine_parts)
    same = BatchScheduler(eng, batch_size=4)
    for i in range(3):
        same.submit(Request(rid=i, prompt=f"same length prompt {i}",
                            max_new=4))
    same.run_to_completion()
    assert same.waves == 1 and same.admitted == 3 and same.len_cuts == 0

    mixed = BatchScheduler(eng, batch_size=4)
    mixed.submit(Request(rid=0, prompt="a short prompt", max_new=4))
    mixed.submit(Request(rid=1, prompt="a much longer prompt than the "
                         "first one was", max_new=4))
    mixed._admit()
    assert int(mixed.live.sum()) == 1 and mixed.len_cuts == 1
    mixed.run_to_completion()
    assert mixed.waves == 2 and mixed.admitted == 2 and mixed.len_cuts == 1


def test_batch_scheduler_slot_wait_grows_while_a_wave_is_in_flight(
        engine_parts):
    """``slot_wait_s`` sums each admitted request's time from ``submit``
    to its slot: a request that arrives behind a live wave waits for it."""
    from repro.serving.engine import BatchScheduler, Request
    eng = make_engine(engine_parts)
    sched = BatchScheduler(eng, batch_size=4)
    sched.submit(Request(rid=0, prompt="the first wave", max_new=4))
    sched._admit()
    alone = sched.slot_wait_s
    assert sched.admitted == 1 and alone < 0.1
    sched.submit(Request(rid=1, prompt="the first wave", max_new=4))
    sched._admit()                       # the wave is live: no admission
    assert sched.admitted == 1
    time.sleep(0.2)
    sched.run_to_completion()
    assert sched.admitted == 2 and sched.waves == 2
    assert sched.slot_wait_s - alone >= 0.2


def test_pipeline_snapshot_carries_the_decode_counters(engine_parts,
                                                       stored):
    """``stats_snapshot()["decode_slots"]`` exposes the slot wait and the
    length cuts beside ``waves`` and ``admitted``."""
    emb, store, qs, rs = stored
    eng = make_engine(engine_parts)
    with BatchedRuntime.from_store(
            store, emb, engine=eng,
            cfg=BatchedRuntimeCfg(max_wait_s=0.005, decode_slots=4)) as rt:
        futs = [rt.submit(p, max_new=4) for p in
                ("a short novel zebra", "a much longer novel zebra prompt "
                 "than the one before it", "a short novel yak")]
        assert all(not f.result(timeout=300).hit for f in futs)
        slots = rt.pipeline_stats()["decode_slots"]
    assert slots["admitted"] == 3 and slots["waves"] >= 2
    assert slots["slot_wait_s"] > 0.0
    assert 1 <= slots["len_cuts"] <= slots["waves"]


def test_submit_temperature_reaches_decode(engine_parts, stored):
    """The facade-level temperature knob flows through submit() to the
    pipelined miss decode (and hits are unaffected by it)."""
    emb, store, qs, rs = stored
    eng = make_engine(engine_parts)
    with BatchedRuntime.from_store(
            store, emb, engine=eng,
            cfg=BatchedRuntimeCfg(max_wait_s=0.005, decode_slots=2)) as rt:
        miss = rt.submit("novel zebra sampled decode", max_new=6,
                         temperature=1.0).result(timeout=300)
        hit = rt.submit(qs[0], temperature=1.0).result(timeout=300)
        assert not miss.hit and miss.response
        assert hit.hit and hit.response == rs[0]


def test_decode_failure_fails_miss_futures_not_hangs(engine_parts, stored):
    """An engine that dies mid-decode must FAIL the affected miss futures
    (and later arrivals) instead of leaving callers blocked; hits keep
    resolving through the untouched search/resolve stages."""
    emb, store, qs, rs = stored
    eng = make_engine(engine_parts)

    def boom(*a, **kw):
        raise RuntimeError("decode exploded")

    eng._decode_chunk = boom
    with BatchedRuntime.from_store(
            store, emb, engine=eng,
            cfg=BatchedRuntimeCfg(max_wait_s=0.005, decode_slots=2)) as rt:
        bad = rt.submit("novel zebra breaks the engine", max_new=4)
        with pytest.raises(RuntimeError, match="decode exploded"):
            bad.result(timeout=60)
        later = rt.submit("another novel zebra arrives later", max_new=4)
        with pytest.raises(RuntimeError):
            later.result(timeout=60)
        ok = rt.submit(qs[0]).result(timeout=60)
        assert ok.hit and ok.response == rs[0]


# ---------------------------------------------------------------------------
# synchronous compatibility path: per-request latency stamping
# ---------------------------------------------------------------------------


def test_query_batch_per_request_latency(engine_parts, stored):
    """The satellite fix: results in one batch no longer share a single
    batch-wide latency — a hit is stamped at search-return, a miss when
    its decode slot retired."""
    emb, store, qs, rs = stored
    eng = make_engine(engine_parts, decode_delay_s=0.05)
    rt = BatchedRuntime.from_store(store, emb, engine=eng)
    with rt:
        res = rt.query_batch([qs[0], "unrelated zebra xylophone"],
                             max_new=8)
    hit, miss = res
    assert hit.hit and not miss.hit
    assert hit.latency_s < miss.latency_s
    assert miss.chunks_run >= 1 and miss.llm_s > 0


# ---------------------------------------------------------------------------
# sequential reference path: search embedding threaded to write-back
# ---------------------------------------------------------------------------


class CountingEmbedder(HashEmbedder):
    def __init__(self):
        super().__init__()
        self.calls = 0

    def encode(self, texts):
        self.calls += 1
        return super().encode(texts)


def test_seq_writeback_reuses_search_embedding(engine_parts, tmp_path):
    """StorInferRuntime.query used to re-encode the query for §3.1
    add_misses even though the race's search already embedded it."""
    cfg, params, tok, run = engine_parts
    eng = make_engine(engine_parts)
    emb = CountingEmbedder()
    store = PrecomputedStore(tmp_path / "s", dim=emb.dim)
    store.add_batch(emb.encode(["hello there"]), ["hello there"], ["hi."])
    store.flush()
    rt = StorInferRuntime(FlatIndex(store.embeddings()), store, emb,
                          engine=eng, cfg=RuntimeCfg(add_misses=True))
    with rt:
        emb.calls = 0
        r = rt.query("completely novel zebra question", max_new=4)
        assert not r.hit and r.response
        assert emb.calls == 1, "write-back re-encoded the query"
        assert store.count == 2


# ---------------------------------------------------------------------------
# MicroBatcher: the submit-after-stop window (satellite)
# ---------------------------------------------------------------------------


def test_microbatcher_rejects_submit_once_stopping():
    """stop() raises the stopping flag BEFORE joining, so a producer can
    no longer enqueue behind the shutdown sentinel (where its future
    would hang forever)."""
    gate = threading.Event()

    def process(subs):
        gate.wait(timeout=10)
        return [s.text for s in subs]

    mb = MicroBatcher(process, max_batch=1, max_wait_s=0.0).start()
    first = mb.submit("in flight")
    time.sleep(0.05)                       # worker picked it up, blocked
    stopper = threading.Thread(target=mb.stop)   # drain; blocks on join
    stopper.start()
    time.sleep(0.1)                        # _stopping is set by now
    with pytest.raises(RuntimeError, match="not running"):
        mb.submit("slipped behind the sentinel")
    gate.set()
    stopper.join(timeout=10)
    assert not stopper.is_alive()
    assert first.result(timeout=10) == "in flight"
