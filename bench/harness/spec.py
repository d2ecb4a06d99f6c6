"""What a run is: the cell, its configuration, its traffic mix and the
metrics it reports, all found by name from ``BENCHMARK.json``.

Nothing here knows a particular cell. A configuration is the JSON file that
its ``BENCHMARK.json`` entry names, and its architecture the module
``bench/arch/<model_type>.py`` (see ``load_arch``); a traffic mix is
``bench/traffic/<mix>.json``; a per-layer metric is the reader
``bench/metrics/<metric>.py`` (see ``load_reader``). Adding any of them
is adding files and entries.
"""
from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path

BENCH_DIR = "bench"
_E2E = re.compile(r"^(hit|miss|all)_p(\d+(?:\.\d+)?)_ms$")
_ARCH_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# what an architecture module provides (see bench/arch/qwen3.py)
ARCH_API = ("program_widths", "init_weights", "forward", "param_count",
            "kv_bytes_per_position", "flops_per_token", "decode_step_cost")


class SpecError(ValueError):
    """The benchmark's data files do not describe a runnable cell."""


def load_benchmark(root: Path) -> dict:
    path = Path(root) / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"{path} not found")
    return json.loads(path.read_text())


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SpecError(f"no {what} named {name!r}; have "
                    f"{sorted(e['name'] for e in entries)}")


def load_cell(root: Path, bench: dict, workload: str) -> dict:
    """The workload entry with its configuration and traffic files loaded
    (``cell["cfg"]``, ``cell["mix"]``)."""
    cell = dict(_by_name(bench["workloads"], workload, "workload"))
    entry = _by_name(bench["configs"], cell["config"], "config")
    cfg_path = Path(root) / entry["file"]
    cell["cfg"] = json.loads(cfg_path.read_text())
    if cell["cfg"].get("name") != entry["name"]:
        raise SpecError(f"{cfg_path} names {cell['cfg'].get('name')!r}, "
                        f"BENCHMARK.json {entry['name']!r}")
    mix_path = Path(root) / BENCH_DIR / "traffic" / f"{cell['traffic']}.json"
    if not mix_path.is_file():
        raise SpecError(f"traffic mix {cell['traffic']!r}: {mix_path} "
                        "not found")
    cell["mix"] = json.loads(mix_path.read_text())
    return cell


def _in_cell(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def end_to_end_for(bench: dict, workload: str) -> list:
    return [m for m in bench["end_to_end"] if _in_cell(m, workload)]


def per_layer_for(bench: dict, workload: str) -> list:
    """Per-layer metrics this cell reports: those that list it, and those
    without a list whose ``moves`` metric the cell reports."""
    e2e = {m["name"] for m in end_to_end_for(bench, workload)}
    return [m for m in bench["per_layer"]
            if (workload in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


def e2e_kind(name: str):
    """How the harness computes an end-to-end metric from its name:
    ``("pct", cls, q)`` for ``<hit|miss|all>_p<q>_ms``, else the name."""
    m = _E2E.match(name)
    if m:
        return ("pct", m.group(1), float(m.group(2)))
    if name in ("output_tokens_per_s", "setup_s"):
        return (name,)
    raise SpecError(f"end-to-end metric {name!r} has no rule in the "
                    "harness")


def load_reader(root: Path, name: str):
    """The module ``bench/metrics/<name>.py`` or, failing that, the one
    named by the part of ``name`` before its first dot (so that
    ``mfu.faq`` and ``mfu.novel``, one quantity split by the end-to-end
    metric it moves, share ``mfu.py``); its ``read(ctx)`` returns the
    metric's value or None when the run gave it nothing to read."""
    metrics = Path(root) / BENCH_DIR / "metrics"
    path = metrics / f"{name}.py"
    if not path.is_file():
        path = metrics / f"{name.split('.')[0]}.py"
    if not path.is_file():
        raise SpecError(f"per-layer metric {name!r}: {metrics / name}.py "
                        "not found")
    return _exec(path, "bench_metric_" + re.sub(r"\W", "_", name),
                 ("read",))


def load_arch(root: Path, cfg: dict):
    """The architecture module ``bench/arch/<model_type>.py`` that the
    configuration names by its ``model_type``: the program widths the file
    states, the plain reference and its control, and the counts of the
    stated work (``ARCH_API``). A module may reuse another's functions by
    loading it here. Each file is executed once a process, so the
    reference's compiled programs are kept from run to run."""
    kind = cfg.get("model_type")
    path = Path(root) / BENCH_DIR / "arch" / f"{kind}.py"
    if not (isinstance(kind, str) and _ARCH_NAME.match(kind)
            and path.is_file()):
        raise SpecError(f"{cfg.get('name')}: model_type {kind!r}: {path} "
                        "not found")
    name = "bench_arch_" + re.sub(r"\W", "_", kind)
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != str(path):
        mod = sys.modules[name] = _exec(path, name, ARCH_API)
    return mod


def _exec(path: Path, name: str, required: tuple):
    """Execute the file ``path`` as the module ``name``; it has to define
    each function in ``required``."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    missing = [f for f in required if not callable(getattr(mod, f, None))]
    if missing:
        raise SpecError(f"{path} lacks {missing}")
    return mod


def load_peaks(root: Path, device_kind: str) -> dict:
    table = json.loads((Path(root) / BENCH_DIR / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SpecError(f"device kind {device_kind!r} is not in "
                        f"{BENCH_DIR}/peaks.json; known: "
                        f"{sorted(table['devices'])}") from None
