"""The benchmark's yardstick on its own: trace reduction, latency and
rate arithmetic, operation and byte counts (Qwen3's from its architecture
module), cell selection and traffic generation. No program runs here."""
from __future__ import annotations

import json
import types

import numpy as np
import pytest

from conftest import QWEN, REPO
from harness import costs, spec, traffic, xtrace

PEAKS = {"flops_per_s": {"bfloat16": 197e12, "int8": 393e12},
         "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
QWEN3 = spec.load_arch(REPO, QWEN)


# -- a small synthetic trace --------------------------------------------------

def _ev(name, start, dur, **stats):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur,
                                 stats=list(stats.items()))


def _line(name, events):
    return types.SimpleNamespace(name=name, events=events)


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=lines)


F1 = "%fusion.1 = bf16[16,2048]{1,0:T(8,128)(2,1)} fusion(bf16[16]{0} %p)"
F2 = "%convert.2 = bf16[28,2048]{1,0:T(8,128)(2,1)} convert(f32[28,2048] %w)"
LOOP = "%while.9 = (s32[]{:T(128)}, f32[16]{0}) while(s32[] %i)"
KERNEL = ("%mips_topk_int8.1 = (f32[293,1,1]{2,1,0}, s32[293,1,1]{2,1,0}) "
          "custom-call(s8[1,384]{1,0} %q), "
          'custom_call_target="tpu_custom_call"')
RED = "%reduce.4 = f32[1]{0} reduce(f32[293]{0} %v)"


def small_trace():
    """Window [1000, 11000) ns. Device ops: a decode chunk program holding
    a loop (2000-5000) around two ops (2000-4000, 3500-5000), a scan
    program with one kernel call (7000-7500) and one other op (7500-7600);
    one op outside the window. Host: the window span, a sleep over the idle
    5000-7000 stretch."""
    host = _plane("/host:CPU", [
        _line("python", [_ev(xtrace.WINDOW_SPAN, 1000, 10000),
                         _ev("bench.wait_schedule", 4900, 2200),
                         _ev("PjitFunction(_decode_chunk_impl)", 1500,
                             400)])])
    dev = _plane("/device:TPU:0", [
        _line("XLA Modules", [_ev("jit__decode_chunk_impl(1)", 2000, 3000),
                              _ev("jit_mips_topk_int8(2)", 7000, 600),
                              _ev("jit__decode_chunk_impl(1)", 20000,
                                  100)]),
        _line("XLA Ops", [
            _ev(LOOP, 2000, 3000), _ev(F1, 2000, 2000), _ev(F2, 3500, 1500),
            _ev(KERNEL, 7000, 500), _ev(RED, 7500, 100),
            _ev(F1, 20000, 100)])])
    return xtrace.reduce_planes([host, dev])


def test_trace_busy_idle_and_programs():
    t = small_trace()
    assert t.window == (1000, 11000)
    assert t.window_s == pytest.approx(10e-6)
    # union of [2000,5000) and [7000,7600): 3600 ns busy
    assert t.busy_s() == pytest.approx(3.6e-6)
    assert t.module_times("decode_chunk") == [pytest.approx(3e-6)]
    assert t.kernel_times("mips_topk_int8") == [pytest.approx(0.5e-6)]
    top = dict(t.top_ops(10))
    assert top["jit__decode_chunk_impl: %fusion.1 fusion bf16[16,2048]"] \
        == pytest.approx(2e-6)
    assert top["jit_mips_topk_int8: %mips_topk_int8.1 custom-call "
               "f32[293,1,1]"] == pytest.approx(0.5e-6)
    # the loop holds the fusions: left out, not counted twice
    assert not any("while" in k for k in top)


def test_trace_idle_gaps_take_the_host_span_that_covers_them():
    gaps = dict(xtrace.reduce_planes(small_trace_planes()).idle_gaps())
    # idle: 1000-2000 (dispatch span covers 400 of 1000 ns: largest
    # overlap), 5000-7000 (sleep covers it), 7600-11000 (nothing)
    assert gaps["bench.wait_schedule"] == pytest.approx(2e-6)
    assert gaps["PjitFunction(_decode_chunk_impl)"] == pytest.approx(1e-6)
    assert gaps["no host span"] == pytest.approx(3.4e-6)


def small_trace_planes():
    t = small_trace()
    host = _plane("/host:CPU", [_line("python", [
        _ev(xtrace.WINDOW_SPAN, 1000, 10000)] + [
        _ev(n, s, e - s) for n, s, e in t.host])])
    dev = _plane("/device:TPU:0", [
        _line("XLA Ops", [_ev(op, s, e - s)
                          for op, m, s, e in t.devices[0].ops])])
    return [host, dev]


def test_trace_without_window_span_is_refused():
    with pytest.raises(ValueError):
        xtrace.reduce_planes([_plane("/host:CPU", [_line("python", [])])])


# -- latency percentiles and rates, timed from the scheduled send -----------

def _rec(kind, due, done, tokens=0, error=None):
    res = types.SimpleNamespace(hit=kind == "hit", token_ids=[1] * tokens)
    return types.SimpleNamespace(req=types.SimpleNamespace(kind=kind),
                                 due=due, done=done,
                                 result=None if error else res, error=error)


def test_latency_counts_from_the_scheduled_send_and_failures_at_deadline():
    import run
    recs = [_rec("hit", 10.0, 10.010), _rec("hit", 10.5, 10.530),
            _rec("miss", 11.0, 13.0, tokens=20),
            _rec("miss", 12.0, 15.5, tokens=30),     # finishes after close
            _rec("miss", 12.5, 0.0, error="boom")]   # never answered
    metrics = [{"name": "hit_p50_ms", "unit": "ms"},
               {"name": "miss_p90_ms", "unit": "ms"},
               {"name": "output_tokens_per_s", "unit": "tokens/s"},
               {"name": "setup_s", "unit": "s"}]
    out = run.end_to_end(metrics, recs, t0=10.0, t_end=15.0, deadline=75.0,
                         setup_s=42.0)
    assert out["hit_p50_ms"]["value"] == pytest.approx(20.0)
    # misses: 2000, 3500 and the failed one at the deadline, 62500 ms
    assert out["miss_p90_ms"]["value"] == pytest.approx(
        np.percentile([2000.0, 3500.0, 62500.0], 90))
    # only the miss finished inside [10, 15) counts: 20 tokens / 5 s
    assert out["output_tokens_per_s"]["value"] == pytest.approx(4.0)
    assert out["setup_s"]["value"] == 42.0


@pytest.mark.parametrize("q", [50, 95])
def test_hit_latency_readers_count_as_the_end_to_end_percentiles(q):
    """The per-layer hit percentiles take the same requests, times and
    failures as an end-to-end ``hit_p<q>_ms`` would."""
    import run
    recs = [_rec("hit", 10.0, 10.010), _rec("hit", 10.5, 10.530),
            _rec("hit", 11.0, 11.200), _rec("miss", 11.0, 13.0, tokens=5),
            _rec("hit", 12.5, 0.0, error="boom")]
    want = run.end_to_end([{"name": f"hit_p{q}_ms", "unit": "ms"}], recs,
                          t0=10.0, t_end=15.0, deadline=75.0, setup_s=0.0)
    ctx = types.SimpleNamespace(recs=recs, deadline=75.0)
    reader = spec.load_reader(REPO, f"hit_latency_p{q}_ms")
    assert reader.read(ctx) == pytest.approx(want[f"hit_p{q}_ms"]["value"])
    assert reader.read(types.SimpleNamespace(recs=recs[3:4],
                                             deadline=75.0)) is None


# -- operations and bytes from shapes ---------------------------------------

def test_param_counts_match_published_sizes():
    # qwen3-1.7b: 1.72 B with the tied head (2.03 B were it untied, as
    # the model card's 2.0 B total counts the embedding twice)
    assert QWEN3.param_count(QWEN) == pytest.approx(1.7205e9, rel=1e-3)
    untied = dict(QWEN, tie_word_embeddings=False)
    assert QWEN3.param_count(untied) - QWEN3.param_count(QWEN) == \
        QWEN["vocab_size"] * QWEN["hidden_size"]


def test_decode_step_bound_is_the_bf16_weights():
    t, bound = costs.decode_step_least_s(QWEN3, QWEN, PEAKS, live=1.0,
                                         kv_positions=40.0)
    assert bound == "memory"
    w = QWEN3.weight_bytes(QWEN)
    assert w == pytest.approx(2 * 1.7205e9, rel=2e-3)
    assert t == pytest.approx((w + 40 * QWEN3.kv_bytes_per_position(QWEN))
                              / 819e9)


def test_qwen3_counts_are_the_numbers_the_benchmark_has_read_by():
    """The counts every Qwen3 roofline and MFU since the first benchmark
    were read by, exactly: moving them into the architecture module
    changed no arithmetic."""
    assert QWEN3.param_count(QWEN) == 1_720_574_976
    assert QWEN3.weight_bytes(QWEN) == 3_441_135_616
    assert QWEN3.kv_bytes_per_position(QWEN) == 114_688
    assert QWEN3.flops_per_token(QWEN, 0) == 3_441_131_520
    assert QWEN3.flops_per_token(QWEN, 100) == 3_464_069_120
    peaks = spec.load_peaks(REPO, "TPU v5 lite")
    t, bound = costs.decode_step_least_s(QWEN3, QWEN, peaks, live=1.135,
                                         kv_positions=45.4)
    assert bound == "memory"
    assert t == (3_441_135_616 + 114_688 * 45.4) / 819e9
    assert round(t * 1e3, 5) == 4.20799


def test_flops_per_token_counts_matmuls_and_attention():
    s = QWEN3.model_shapes(QWEN)
    f0 = QWEN3.flops_per_token(QWEN, 0)
    f9 = QWEN3.flops_per_token(QWEN, 9)
    assert f9 - f0 == 4 * s["layers"] * s["h"] * s["hd"] * 9
    # the gated MLP's three matmuls in each of 28 layers, and the head
    assert f0 > 2 * (28 * 3 * 2048 * 6144 + 151936 * 2048)


def test_scan_bound_is_the_store_bytes_below_hundreds_of_queries():
    least, bound = costs.scan_least_s(150_016, 384, 32, PEAKS)
    assert bound == "memory"
    assert least == pytest.approx((150_016 * 388 + 32 * 388 + 32 * 8)
                                  / 819e9)
    _, bound = costs.scan_least_s(150_016, 384, 4096, PEAKS)
    assert bound == "compute"


def _ctx(**kw):
    base = dict(cfg=QWEN, arch=QWEN3, peaks=PEAKS, window_s=10.0, t0=0.0,
                t_end=10.0, recs=[], trace=None, store_rows=150_016,
                snap0={"stages": {"search": {"items": 0, "mean_wait_ms": 0},
                                  "resolve": {"items": 0,
                                              "mean_wait_ms": 0}},
                       "search_batches": 0,
                       "decode_slots": {"waves": 2, "admitted": 2}},
                snap1={"stages": {"search": {"items": 10,
                                             "mean_wait_ms": 3.0},
                                  "resolve": {"items": 4,
                                              "mean_wait_ms": 1.0}},
                       "search_batches": 5,
                       "decode_slots": {"waves": 12, "admitted": 17}})
    base.update(kw)
    return types.SimpleNamespace(**base)


def _miss(prompt_len, n, done):
    r = _rec("miss", 0.0, done, tokens=n)
    r.req.prompt_len = prompt_len
    return r


def test_readers_from_counters_and_shapes():
    ctx = _ctx(recs=[_miss(8, 40, 5.0), _miss(12, 10, 11.0)])
    read = lambda name: spec.load_reader(REPO, name).read(ctx)  # noqa: E731
    assert read("search_wait_ms") == pytest.approx(3.0)
    assert read("decode_wave_size.novel") == pytest.approx(1.5)
    flops = sum(QWEN3.flops_per_token(QWEN, 8 + i) for i in range(40))
    scan = 2 * 150_016 * 384 * 10
    want = 100 * (flops / 197e12 + scan / 393e12) / 10
    assert read("mfu.novel") == pytest.approx(want)
    assert read("mfu.faq") == read("mfu.novel")
    # nothing traced: the device metrics have nothing to read
    assert read("decode_roofline.novel") is None
    assert read("device_idle_share.novel") is None


def test_roofline_readers_stay_under_100_at_the_least_time():
    t = small_trace()
    # a decode step as fast as the bound, and a scan as fast as its bound
    least_step, _ = costs.decode_step_least_s(QWEN3, QWEN, PEAKS, 1.0, 8.0)
    t.devices[0].modules = [("jit__decode_chunk_impl", 0,
                             int(round(least_step * 8 * 1e9)))]
    least_scan, _ = costs.scan_least_s(150_016, 384, 2.0, PEAKS)
    t.devices[0].ops = [(KERNEL, "jit_mips_topk_int8", 0,
                         int(round(least_scan * 1e9)))] * 5
    ctx = _ctx(trace=t, recs=[_miss(8, 1, 5.0)])
    share = spec.load_reader(REPO, "decode_roofline.novel").read(ctx)
    assert share == pytest.approx(100.0, rel=1e-3)
    scan = spec.load_reader(REPO, "mips_roofline").read(ctx)
    assert scan == pytest.approx(100.0, rel=1e-3)


# -- cells and metrics by name ------------------------------------------------

def test_each_cell_reports_setup_another_end_to_end_and_a_layer():
    bench = spec.load_benchmark(REPO)
    for w in bench["workloads"]:
        e2e = {m["name"] for m in spec.end_to_end_for(bench, w["name"])}
        assert "setup_s" in e2e and len(e2e) >= 2
        layers = spec.per_layer_for(bench, w["name"])
        assert layers and all(m["moves"] in e2e for m in layers)
        for m in layers:
            spec.load_reader(REPO, m["name"])
        for m in e2e:
            spec.e2e_kind(m)


def test_every_configuration_names_an_architecture_module():
    bench = spec.load_benchmark(REPO)
    for entry in bench["configs"]:
        cfg = json.loads((REPO / entry["file"]).read_text())
        assert spec.load_arch(REPO, cfg).param_count(cfg) > 0


def test_an_unknown_architecture_is_refused_with_the_missing_path():
    with pytest.raises(spec.SpecError, match="bench/arch/nope.py not found"):
        spec.load_arch(REPO, dict(QWEN, model_type="nope"))
    with pytest.raises(spec.SpecError, match="not found"):
        spec.load_arch(REPO, dict(QWEN, model_type="../harness/costs"))


def test_unknown_device_kind_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_peaks(REPO, "cpu")
    assert spec.load_peaks(REPO, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9


# -- traffic ------------------------------------------------------------------

MIX = json.loads((REPO / "bench/traffic/faq.json").read_text())
FACTS = [traffic.Fact(f"entity {i}", f"relation {i % 7}", f"value {i % 5}",
                      rank) for i, rank in
         enumerate(np.random.default_rng(0).permutation(200))]
USERS = traffic.Users(QWEN["users"], FACTS)
STORED = {q for q, _ in traffic.stored_pairs(USERS, 0, 150)}


def _is_hit(texts):
    """A stand-in for the store's scan: a hit is a stored question."""
    return np.asarray([t in STORED for t in texts])


def _len(text):
    return 1 + len(text.split())


def _sched(n=200):
    return traffic.schedule(MIX, n, USERS, _is_hit, _len,
                            [11, 12, 12, 13, 13, 13, 14, 14, 15, 16])


def _plan(seed, n=200):
    return traffic.plan(_sched(n), seed, USERS, _is_hit, _len)


def test_same_seed_same_plan_and_large_seeds_work():
    big = 2 ** 31 + 12345
    a = _plan(big)
    b = _plan(big)
    assert [(r.kind, r.text, r.max_new) for r in a] == \
        [(r.kind, r.text, r.max_new) for r in b]


def test_every_seed_gets_the_same_schedule_with_its_own_texts():
    a = _plan(1)
    b = _plan(2)
    key = lambda r: (r.kind, r.prompt_len, r.max_new)  # noqa: E731
    assert list(map(key, a)) == list(map(key, b))
    assert [r.text for r in a] != [r.text for r in b]
    # the mix's hit share in every block of the schedule
    per_block = MIX["hit_share"] * traffic.BLOCK
    for lo in range(0, 200, traffic.BLOCK):
        blk = a[lo:lo + traffic.BLOCK]
        assert abs(sum(r.kind == "hit" for r in blk) - per_block) < 1
    # each text has its slot's class and, for a miss, its length
    assert all(_is_hit([r.text])[0] == (r.kind == "hit") for r in a)
    misses = [r for r in a if r.kind == "miss"]
    assert all(_len(r.text) == r.prompt_len for r in misses)
    assert {r.max_new for r in a} <= {11, 12, 13, 14, 15, 16}
    # the users' phrasings, hard ones among the misses
    hard = [t.split("{")[0] for t in QWEN["users"]["hard_templates"]]
    assert any(r.text.startswith(tuple(hard)) for r in misses)


def test_stored_pairs_are_distinct_anticipated_phrasings_with_answers():
    pairs = traffic.stored_pairs(USERS, 0, 150)
    assert len({q for q, _ in pairs}) == 150
    by_q = {}
    for f in FACTS:
        for t in QWEN["users"]["templates"]:
            by_q[t.format(r=f.relation, e=f.entity)] = f.answer()
    fillers = sorted(QWEN["users"]["fillers"], key=len, reverse=True)
    for q, a in pairs:
        core = next(q[len(x):] for x in fillers if q.startswith(x))
        assert by_q[core] == a


def test_open_loop_arrivals_hold_the_rate_inside_the_window():
    mix = MIX
    n = traffic.n_requests(mix, 51)
    t = traffic.arrivals(mix, _sched(n), 51.0)
    assert len(t) == n == round(mix["rate_per_s"] * 51)
    assert np.all(np.diff(t) > 0) and 0 < t[0] and t[-1] < 51.0
    gaps = np.diff(np.concatenate([[0], t]))
    # a Poisson sample: exponential gaps, coefficient of variation near 1
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, abs=0.1)
