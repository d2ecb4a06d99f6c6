"""The system under test, opened the way its users open it.

The store holds the configuration's stored pairs, which the benchmark
renders itself from the knowledge base with the users model
(``traffic.stored_pairs``), embedded by the program's embedder and topped
up with seeded filler rows; it is cached under
``experiments/bench_store/<config>/`` in the checkout, so only a cell's
first run builds it. The reference keeps its own copy of the pairs, so it
reads nothing back from the program's store. The serving side is
``StorInfer.open`` with a ``SystemCfg`` made from the configuration file:
the benchmark never builds an engine of its own.
"""
from __future__ import annotations

import dataclasses
import json
import shutil
import sys
from pathlib import Path

import numpy as np

STORE_DIR = Path("experiments") / "bench_store"
_DONE = "bench_store_complete"


def program_on_path(root: Path):
    src = Path(root) / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"{src / 'repro'} not found: the program "
                                "under test is not in this checkout")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))


def filler_rows(store_cfg: dict, n_kb: int) -> np.ndarray:
    """The seeded filler rows that top the store up to its row count:
    unit-norm Gaussian vectors, float32."""
    rng = np.random.default_rng(store_cfg["filler_seed"])
    fill = rng.standard_normal((store_cfg["rows"] - n_kb, store_cfg["dim"]),
                               dtype=np.float32)
    fill /= np.linalg.norm(fill, axis=1, keepdims=True)
    return fill


def knowledge(cfg: dict):
    """The configuration's knowledge base as the benchmark sees it: (its
    facts, the users model over them, the stored pairs, the corpus texts
    and the program's tokenizer built from them)."""
    from repro.core.kb import build_kb
    from repro.core.tokenizer import Tokenizer

    from .traffic import Fact, Users, stored_pairs
    sc = cfg["store"]
    kb = build_kb(sc["kb"], seed=sc["kb_seed"])
    facts = [Fact(f.entity, f.relation, f.value, int(r))
             for f, r in zip(kb.facts, kb.popularity)]
    users = Users(cfg["users"], facts)
    pairs = stored_pairs(users, sc["pairs_seed"], sc["stored_pairs"])
    texts = [d.text() for d in kb.docs]
    return facts, users, pairs, texts, Tokenizer.from_texts(texts)


def ensure_store(root: Path, cfg: dict, pairs, tok, log) -> Path:
    """The configuration's store, built on first use: the stored pairs
    through the program's embedder, then the filler rows."""
    from repro.api import make_embedder
    from repro.core.store import PrecomputedStore
    sc, sv = cfg["store"], cfg["serving"]
    path = Path(root) / STORE_DIR / cfg["name"]
    if (path / _DONE).is_file():
        return path
    if path.exists():
        shutil.rmtree(path)             # a build that did not finish
    emb = make_embedder(sv["embedder"], tokenizer=tok)
    store = PrecomputedStore(path, dim=sc["dim"], emb_dtype=sc["dtype"])
    qs = [q for q, _ in pairs]
    store.add_batch(emb.encode(qs), qs, [a for _, a in pairs])
    fill = filler_rows(sc, len(pairs))
    store.add_batch(fill, [f"filler query {i}" for i in range(len(fill))],
                    [f"filler response {i}" for i in range(len(fill))])
    store.close()
    (path / _DONE).write_text(json.dumps({"pairs": len(pairs),
                                          "filler": len(fill)}))
    log(f"store built: {len(pairs)} pairs + {len(fill)} filler rows at "
        f"{path}")
    return path


def register_model(cfg: dict, arch) -> str:
    """Register the configuration's model under its own name: the
    program's architecture with the file's overrides (a depth cut), after
    checking that every width the file states, as the architecture module
    ``arch`` maps it onto the program's config, agrees with the program."""
    from repro.configs.base import get_config, register
    base = get_config(cfg["program_arch"])
    mc = dataclasses.replace(base, name=cfg["name"],
                             **cfg.get("program_overrides", {}))
    stated = arch.program_widths(cfg)
    bad = {k: (getattr(mc, k), v) for k, v in stated.items()
           if getattr(mc, k) != v}
    if bad:
        raise ValueError(f"{cfg['name']}: the program's {cfg['program_arch']}"
                         f" differs from the configuration file: {bad}")
    register(mc)
    return mc.name


def open_system(root: Path, cfg: dict, arch, store: Path, tok,
                weights_seed: int):
    from repro.api import EngineCfg, StorInfer, SystemCfg
    from repro.core.runtime import BatchedRuntimeCfg
    sv = cfg["serving"]
    name = register_model(cfg, arch)
    scfg = SystemCfg(
        index=sv["index"], embedder=sv["embedder"], s_th_run=sv["s_th_run"],
        decode_slots=sv["decode_slots"],
        batched=BatchedRuntimeCfg(add_misses=sv["write_back"]),
        engine=EngineCfg(arch=name, smoke=False, max_len=sv["max_len"],
                         chunk=sv["chunk"], seed=weights_seed))
    return StorInfer.open(store, scfg, tokenizer=tok)
