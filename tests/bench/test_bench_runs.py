"""Whole runs of the harness on the CPU at a tiny size: the real program
served through ``StorInfer.submit``, traffic from the data files, the
reference check, and the command line's refusals."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import REPO, TINY_CFG, run_cell
from harness import spec


def test_open_loop_cell_runs_correct_with_its_end_to_end_metrics(
        tiny_root, no_cache):
    res = run_cell(tiny_root, "tiny.t1k.tfaq", 2 ** 31 + 99)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 24
    assert set(res["metrics"]) == {"hit_p80_ms", "miss_p90_ms", "setup_s"}
    assert res["compiles_in_window"] == 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]


def test_a_new_metric_is_a_file_and_an_entry(tiny_root, no_cache):
    """A later change adds a per-layer metric by adding its reader and a
    BENCHMARK.json entry; the harness finds it by name. Off a TPU the
    device-trace metrics find nothing to read and are left out."""
    (tiny_root / "bench/metrics/probe_admitted.tnovel.py").write_text(
        "def read(ctx):\n"
        "    a, b = ctx.snap0['decode_slots'], ctx.snap1['decode_slots']\n"
        "    return b['admitted'] - a['admitted']\n")
    path = tiny_root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["per_layer"].append({
        "name": "probe_admitted.tnovel", "unit": "requests",
        "better": "higher", "source": "program_counter",
        "layer": "decode scheduler", "moves": "output_tokens_per_s",
        "workloads": ["tiny.t1k.tnovel"]})
    path.write_text(json.dumps(bench))
    res = run_cell(tiny_root, "tiny.t1k.tnovel", 5, trace=1)
    assert res["correct"] is True, res["checks"]
    assert res["metrics"]["probe_admitted.tnovel"]["value"] > 0
    assert "decode_wave_size.novel" in res["metrics"]
    for name in ("decode_roofline.novel", "mfu.novel",
                 "device_idle_share.novel"):
        assert name not in res["metrics"]
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


TOY_ARCH = '''"""A toy architecture: Qwen3's layer without the query and key
norms, made of the Qwen3 module's functions."""
from pathlib import Path

from harness import spec

qwen3 = spec.load_arch(Path(__file__).parents[2], {"model_type": "qwen3"})
FORWARDS = []
param_count = qwen3.param_count
kv_bytes_per_position = qwen3.kv_bytes_per_position
flops_per_token = qwen3.flops_per_token
decode_step_cost = qwen3.decode_step_cost
init_weights = qwen3.init_weights


def program_widths(cfg):
    return qwen3.program_widths(dict(cfg, qk_norm=False))


def forward(cfg, w, tokens, fp8=False):
    FORWARDS.append(tuple(tokens.shape))
    return qwen3.forward(dict(cfg, qk_norm=False), w, tokens, fp8)
'''


def _add_config(root, name, model_type, **program_overrides):
    """A configuration of the tiny widths named ``name`` with its own
    ``model_type``, and its cell under the tiny open-loop mix."""
    cfg = {k: v for k, v in TINY_CFG.items() if k != "qk_norm"}
    cfg.update(name=name, model_type=model_type,
               program_overrides=dict(TINY_CFG["program_overrides"],
                                      **program_overrides))
    (root / f"bench/configs/{name}.json").write_text(json.dumps(cfg))
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    bench["configs"].append({"name": name, "source": "test",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": f"{name}.tfaq", "config": name,
                               "traffic": "tfaq", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"]:
        if "tiny.t1k.tfaq" in m.get("workloads", ()):
            m["workloads"].append(f"{name}.tfaq")
    path.write_text(json.dumps(bench))


def test_a_new_architecture_is_a_file_and_an_entry(tiny_root, no_cache):
    """A later change adds an architecture by adding its module under
    ``bench/arch/``, a configuration naming it by ``model_type`` and the
    BENCHMARK.json entries; no harness file changes. Its reference judges
    the served tokens, and a wrong width mapping would refuse the run."""
    (tiny_root / "bench/arch/toy.py").write_text(TOY_ARCH)
    _add_config(tiny_root, "toy.t1k", "toy", qk_norm=False)
    res = run_cell(tiny_root, "toy.t1k.tfaq", 2 ** 31 + 7)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0
    assert res["checks"]["logit_gap"]["value"] <= \
        res["checks"]["logit_gap"]["limit"]
    assert sys.modules["bench_arch_toy"].FORWARDS


def test_an_unknown_architecture_stops_the_run_before_the_program(
        tiny_root, no_cache):
    _add_config(tiny_root, "nope.t1k", "nope")
    with pytest.raises(spec.SpecError, match="bench/arch/nope.py not found"):
        run_cell(tiny_root, "nope.t1k.tfaq", 3)
    assert not (tiny_root / "experiments/bench_store/nope.t1k").exists()


def _cli(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "qwen3-1.7b.p150k.faq", "--seed", "1", "--seconds", "1",
         *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_without_a_tpu_no_result_and_a_nonzero_exit():
    for trace in ("0", "1"):
        p = _cli(REPO, "--trace", trace)
        assert p.returncode != 0
        assert "needs a TPU" in p.stderr
        assert "{" not in p.stdout


def test_benchmark_files_alone_are_not_a_run(tmp_path):
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0
    assert "{" not in p.stdout
