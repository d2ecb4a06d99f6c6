"""Whole runs of the harness on the CPU at a tiny size: the real program
served through ``StorInfer.submit``, traffic from the data files, the
reference check, and the command line's refusals."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

from conftest import REPO, run_cell


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
