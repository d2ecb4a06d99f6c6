"""DeepSeek-V2-Lite in the benchmark: the architecture module's counts at
the configuration's share, the program's engine against its plain
reference at a tiny width, a tiny configuration of the architecture served
and judged through the harness, and the expert-layer reader."""
from __future__ import annotations

import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import REPO, TINY_CFG, run_cell
from harness import costs, spec, system
from harness import reference as R

DSV2 = json.loads((REPO / "bench/configs/deepseek-v2-lite.e8.p150k.json")
                  .read_text())
ARCH = spec.load_arch(REPO, DSV2)
PEAKS = spec.load_peaks(REPO, "TPU v5 lite")

# DeepSeek-V2-Lite's layers at test widths: a leading dense layer and two
# expert layers holding 4 of 16 routed experts, top-3, 2 shared; latent
# rank 32, rope head 8; the word-level vocabulary of the tiny cells
TINY_WIDTHS = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, intermediate_size=128, moe_intermediate_size=32,
    num_hidden_layers=3, published_n_routed_experts=16, n_routed_experts=4,
    expert_parallel=4, num_experts_per_tok=3, vocab_size=8512)


def tiny_dsv2(name="tinyds.t1k", expert_offset=0):
    cfg = dict(DSV2, **TINY_WIDTHS, name=name, expert_offset=expert_offset)
    cfg.update({k: TINY_CFG[k] for k in ("store", "serving", "source")})
    cfg["limits"] = {"logit_gap": 0.05}
    cfg["program_overrides"] = dict(
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        d_ff_dense=128, d_ff_expert=32, n_experts=16, n_experts_held=4,
        expert_offset=expert_offset, experts_per_tok=3, vocab_size=8512)
    return cfg


# -- counts --------------------------------------------------------------------

def test_counts_at_the_share_are_the_configurations():
    """8 of 64 experts a layer: 3.11 B parameters (6.22 GB in bf16), a
    31,104-byte latent cache a position, and a decode step's least time
    from 2.20 GB outside the routed experts plus 17.3 MB a held expert."""
    assert ARCH.param_count(DSV2) == 3_110_989_312
    assert ARCH.kv_bytes_per_position(DSV2) == 27 * 576 * 2 == 31_104
    assert ARCH.weight_bytes_outside_experts(DSV2) == 2 * 1_101_917_696
    assert ARCH.experts_touched(DSV2, 1) == pytest.approx(8 * 6 / 64)
    assert ARCH.experts_touched(DSV2, 16) == pytest.approx(
        8 * (1 - (58 / 64) ** 16))

    def least_ms(live):
        t, bound = costs.decode_step_least_s(ARCH, DSV2, PEAKS, live, 0.0)
        assert bound == "memory"
        return 1e3 * t
    assert least_ms(16) == pytest.approx(6.18, abs=0.005)
    assert least_ms(1.13) == pytest.approx(3.15, abs=0.005)
    every = (2 * 1_101_917_696 + 26 * 8 * 3 * 2048 * 1408 * 2) / 819e9
    assert every * 1e3 == pytest.approx(7.08, abs=0.005)
    assert least_ms(200) == pytest.approx(every * 1e3)


def test_flops_count_the_held_share_of_the_routed_experts():
    f0 = ARCH.flops_per_token(DSV2, 0)
    whole = dict(DSV2, n_routed_experts=64, expert_parallel=1)
    # six routed experts a token at the whole layer, 6 x 8 / 64 at the share
    routed = 26 * 3 * 2048 * 1408 * 2
    assert ARCH.flops_per_token(whole, 0) - f0 == pytest.approx(
        routed * (6 - 0.75))
    assert ARCH.flops_per_token(DSV2, 9) - f0 == \
        2 * 27 * 16 * (192 + 128) * 9


def test_program_widths_are_the_registered_model_with_the_share():
    from repro.configs import get_config
    stated = ARCH.program_widths(DSV2)
    assert stated["n_experts"] == 64 and stated["n_experts_held"] == 8
    name = system.register_model(DSV2, ARCH)
    mc = get_config(name)
    assert (mc.n_layers, mc.d_model, mc.kv_lora_rank, mc.d_ff_expert,
            mc.d_ff_dense, mc.vocab_size) == (27, 2048, 512, 1408, 10944,
                                              102400)
    assert mc.param_count() == ARCH.param_count(DSV2) - 27 * (512 + 4096) \
        - 2048
    bad = dict(DSV2, rope_scaling=dict(DSV2["rope_scaling"], factor=4))
    with pytest.raises(ValueError, match="rope_factor"):
        system.register_model(bad, ARCH)


# -- the engine against the reference ------------------------------------------------

@pytest.mark.parametrize("offset", [0, 12])
def test_engine_prefill_then_decode_agrees_with_the_reference(offset):
    """At f32 weights the engine's prefill and then its decode steps through
    the latent cache give the reference's full-forward logits (to 1e-4 of
    the largest logit: the orders of summation differ, nothing else)."""
    from repro.configs import get_config
    from repro.core.tokenizer import Tokenizer
    from repro.models import model as M
    from repro.serving.engine import Engine
    cfg = tiny_dsv2(f"tinyds.o{offset}", expert_offset=offset)
    mc = get_config(system.register_model(cfg, ARCH))
    params = M.init_model(jax.random.PRNGKey(21), mc, dtype=jnp.float32)
    e = Engine(mc, params, Tokenizer(["x"]), max_len=24, chunk=2)
    ids = list(np.random.default_rng(offset).integers(4, 8000, 14))
    n0 = 9
    logits, one = e._prefill(params, jnp.asarray([ids[:n0]], jnp.int32))
    cache = e._write_slot(M.init_cache(mc, 2, e.max_len, jnp.float32), one,
                          jnp.asarray(1, jnp.int32))
    got = [np.asarray(logits[0, -1])]
    for i in range(n0, len(ids)):
        tok = jnp.asarray([[0], [ids[i]]], jnp.int32)
        step, cache, _ = M.decode_step(mc, params, tok, cache,
                                       jnp.asarray(i, jnp.int32), e.run)
        got.append(np.asarray(step[1, 0]))
    got = np.stack(got)[:, :cfg["vocab_size"]]
    w = ARCH.init_weights(cfg, 21)
    with jax.default_matmul_precision("highest"):
        ref = R._logits(ARCH.forward, R._frozen(cfg), w,
                        jnp.asarray([ids], jnp.int32), False)
    want = np.asarray(ref[0, n0 - 1:])
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


# -- through the harness ---------------------------------------------------------------

def test_a_deepseek_configuration_runs_correct_through_the_harness(
        tiny_root, no_cache):
    """A tiny configuration of the architecture, its cell under the tiny
    closed-loop mix, served through the program and judged by this
    module's reference; the expert-layer reader reads the scheduler's
    counters, a share of the held experts read."""
    cfg = tiny_dsv2()
    (tiny_root / "bench/configs/tinyds.t1k.json").write_text(json.dumps(cfg))
    path = tiny_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    cell = "tinyds.t1k.tnovel"
    bench["configs"].append({"name": "tinyds.t1k", "source": "test",
                             "file": "bench/configs/tinyds.t1k.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": cell, "config": "tinyds.t1k",
                               "traffic": "tnovel", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "deepseek-v2-lite.e8.p150k.novel" in m.get("workloads", ()):
            m["workloads"].append(cell)
    path.write_text(json.dumps(bench))
    res = run_cell(tiny_root, cell, 2 ** 31 + 11, trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] <= 0.05
    assert 0 < res["metrics"]["experts_touched.dsv2"]["value"] <= 100
    assert res["metrics"]["decode_wave_size.novel"]["value"] >= 1


# -- the expert-layer reader ----------------------------------------------------------

def _snaps(a, b):
    return types.SimpleNamespace(snap0={"decode_slots": a},
                                 snap1={"decode_slots": b})


def test_experts_touched_reads_the_counter_deltas():
    read = spec.load_reader(REPO, "experts_touched.dsv2").read
    a = {"waves": 1, "admitted": 1, "experts_touched": 40,
         "moe_layer_steps": 52, "experts_read": 8 * 52}
    b = {"waves": 9, "admitted": 9, "experts_touched": 40 + 312,
         "moe_layer_steps": 52 + 26 * 8 * 3,
         "experts_read": 8 * (52 + 26 * 8 * 3)}
    assert read(_snaps(a, b)) == pytest.approx(100 * 312 / (8 * 624))


def test_experts_touched_reads_nothing_without_the_counters():
    read = spec.load_reader(REPO, "experts_touched.dsv2").read
    old = {"waves": 1, "admitted": 1, "slot_wait_s": 0.0, "len_cuts": 0}
    assert read(_snaps(old, dict(old, waves=4))) is None
    none = dict(old, experts_touched=0, moe_layer_steps=0, experts_read=0)
    assert read(_snaps(none, none)) is None
    assert read(types.SimpleNamespace(snap0={}, snap1={})) is None
