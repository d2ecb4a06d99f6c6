"""Fixtures for the benchmark harness's CPU tests: a throwaway checkout
with a tiny configuration and tiny traffic mixes, served by the real
harness and the real program at a size the CPU runs in seconds."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

QWEN = json.loads((REPO / "bench/configs/qwen3-1.7b.p150k.json")
                  .read_text())

TINY_CFG = {
    "name": "tiny.t1k",
    "source": "a test configuration: qwen3 layers at test widths",
    "program_arch": "qwen3-1.7b",
    "program_overrides": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                          "n_kv_heads": 2, "d_ff": 128, "head_dim": 16,
                          "vocab_size": 8512},
    "model_type": "qwen3", "hidden_size": 64, "intermediate_size": 128,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 8512,
    "hidden_act": "silu", "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "tie_word_embeddings": True, "attention_bias": False, "qk_norm": True,
    "torch_dtype": "bfloat16",
    "store": {"kb": "squad", "kb_seed": 0, "stored_pairs": 120,
              "pairs_seed": 0, "rows": 1024, "dim": 384, "dtype": "int8",
              "filler_seed": 0},
    "users": QWEN["users"],
    "serving": {"index": "flat", "embedder": "hash", "s_th_run": 0.9,
                "decode_slots": 4, "max_len": 80, "chunk": 4,
                "write_back": False},
    "limits": {"logit_gap": 0.05},
    "reduced": [],
}

TINY_MIXES = {
    "tfaq": {"loop": "open", "rate_per_s": 12.0, "hit_share": 0.5,
             "schedule_seed": 11, "length_sample": 1000, "drain_s": 60,
             "check_hits": 16, "check_misses": 4},
    "tnovel": {"loop": "closed", "clients": 6, "max_rate_per_s": 100,
               "hit_share": 0.2, "schedule_seed": 11,
               "length_sample": 1000, "drain_s": 60, "check_hits": 8,
               "check_misses": 4},
}


def make_root(base: Path) -> Path:
    """A checkout holding the program (linked), the real harness's data
    files, and a tiny configuration with two tiny cells."""
    root = base / "checkout"
    (root / "bench").mkdir(parents=True)
    (root / "src").symlink_to(REPO / "src")
    for sub in ("traffic", "metrics", "configs", "arch"):
        shutil.copytree(BENCH / sub, root / "bench" / sub)
    shutil.copy(BENCH / "peaks.json", root / "bench" / "peaks.json")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    (root / "bench" / "configs" / "tiny.t1k.json").write_text(
        json.dumps(TINY_CFG))
    bench["configs"].append({"name": "tiny.t1k", "source": "test",
                             "file": "bench/configs/tiny.t1k.json",
                             "reduced": [], "why": "test"})
    for mix, body in TINY_MIXES.items():
        (root / "bench" / "traffic" / f"{mix}.json").write_text(
            json.dumps(body))
        bench["workloads"].append({"name": f"tiny.t1k.{mix}",
                                   "config": "tiny.t1k", "traffic": mix,
                                   "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads")
        if cells is None:
            continue
        if any(c.endswith(".faq") for c in cells):
            cells.append("tiny.t1k.tfaq")
        if any(c.endswith(".novel") for c in cells):
            cells.append("tiny.t1k.tnovel")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture
def no_cache():
    """Keep a run's compile-cache settings out of the rest of the test
    process: the cache directory the harness sets in its checkout and the
    threshold it lowers are restored, and the cache is closed."""
    import jax
    from jax._src import compilation_cache
    was_dir = jax.config.jax_compilation_cache_dir
    was = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", was_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was)
    compilation_cache.reset_cache()


def run_cell(root, workload, seed, seconds=2.0, trace=0, **kw):
    import run
    return run.run(["--workload", workload, "--seed", str(seed),
                    "--seconds", str(seconds), "--trace", str(trace)],
                   root=root, require_chip=False, **kw)
