"""The comparison that decides ``correct``.

Once the window has closed and the program is freed, the requests the
window finished are judged by the plain reference (``reference.py``, and
the configuration's architecture module for the model):
every route, and a sample drawn from the seed of the answers (hits up to
the mix's count; misses with the longest among them):

* ``route_wrong``: finished requests whose hit/miss decision differs from
  the reference's exact int8 scan against the hit threshold (limit 0);
* ``hit_wrong``: hits whose returned pair is not one of the pairs at the
  reference's best score (limit 0);
* ``logit_gap``: over every served token of the sampled misses, the widest
  gap by which its reference logit lies below the reference's best at that
  position (limit from the configuration file, set from the readings of
  sound runs and of the float8 control, see PERF.md). A miss that stopped
  before its budget served EOS at the next position, and is judged on it.
  A prompt keeps the first ``max_len - 1 - max_new`` of its tokens, the
  budget the configuration's ``max_len`` leaves it.

The reference's stored pairs are the benchmark's own (``traffic.
stored_pairs``), never read back from the program's store. With
``control`` the float8 control stands in for the program: at each served
position the token the control puts first is judged in place of the
served one, so ``logit_gap`` is the control's reading and ``correct``
its verdict; the program's reading is kept beside it.
"""
from __future__ import annotations

import numpy as np

from . import reference as R
from .traffic import pick


class RefStore:
    """The reference's own int8 copy of the configuration's store: its
    stored pairs, then (unless ``filler`` is None) the filler rows."""

    def __init__(self, store_cfg: dict, pairs, filler):
        self.pairs = list(pairs)
        dim = store_cfg["dim"]
        kb8, kbs = R.quantize(R.hash_embed([q for q, _ in pairs], dim))
        if filler is None:
            self.x8, self.xs = kb8, kbs
        else:
            f8, fs = R.quantize(filler)
            self.x8 = np.concatenate([kb8, f8])
            self.xs = np.concatenate([kbs, fs])
        self.dim = dim

    def pair(self, row: int):
        if row < len(self.pairs):
            return self.pairs[row]
        i = row - len(self.pairs)
        return (f"filler query {i}", f"filler response {i}")

    def best(self, texts):
        """(best score, rows at that score) per text."""
        q8, qs = R.quantize(R.hash_embed(texts, self.dim))
        s = R.scan_scores(q8, qs, self.x8, self.xs)
        top = s.max(axis=1)
        return top, [np.flatnonzero(s[i] == top[i]) for i in range(len(s))]


def served_ids(rec) -> list:
    ids = list(rec.result.token_ids)
    return ids + [R.EOS] if len(ids) < rec.req.max_new else ids


def judge(cfg: dict, arch, mix: dict, seed: int, recs, ref_store: RefStore,
          vocab: dict, weights_seed: int, *, control: bool = False) -> dict:
    """The numbers compared, each with its limit, the model's by the
    architecture module ``arch``; with ``control`` the logit gap is the
    float8 control's and ``program_logit_gap`` the program's."""
    ok = [r for r in recs if r.done and r.error is None]
    hits = [r for r in ok if r.result.hit]
    misses = [r for r in ok if not r.result.hit]
    hit_s = pick(seed, hits, mix["check_hits"])
    longest = max(misses, key=lambda r: len(r.result.token_ids), default=None)
    miss_s = pick(seed, misses, mix["check_misses"],
                  must=[longest] if longest else [])
    th = cfg["serving"]["s_th_run"]
    route_wrong = hit_wrong = 0
    if ok:
        top, _ = ref_store.best([r.req.text for r in ok])
        route_wrong = int(sum(bool(r.result.hit) != bool(t >= th)
                              for r, t in zip(ok, top)))
    if hit_s:
        _, rows = ref_store.best([r.req.text for r in hit_s])
        for r, rs in zip(hit_s, rows):
            got = (r.result.matched_query, r.result.response)
            hit_wrong += int(got not in {ref_store.pair(int(i)) for i in rs})
    out = {"route_wrong": {"value": route_wrong, "limit": 0},
           "hit_wrong": {"value": hit_wrong, "limit": 0},
           "routes_checked": len(ok), "hits_checked": len(hit_s),
           "misses_checked": len(miss_s)}
    if miss_s:
        room = cfg["serving"]["max_len"] - 1
        seqs = [(R.encode(r.req.text, vocab)[:room - r.req.max_new],
                 served_ids(r)) for r in miss_s]
        w = arch.init_weights(cfg, weights_seed)
        prog, ctrl = R.served_gaps(cfg, arch.forward, w, seqs,
                                   cfg["serving"]["max_len"],
                                   control=control)
        del w
        gap = float(max(g.max() for g in prog))
        if control:
            out["program_logit_gap"] = gap
            gap = float(max(g.max() for g in ctrl))
        out["logit_gap"] = {"value": gap,
                            "limit": cfg["limits"]["logit_gap"]}
        out["tokens_checked"] = int(sum(len(g) for g in prog))
    return out


def correct(checks: dict, mix: dict) -> bool:
    """Every compared number within its limit, and each kind of request
    the mix sends represented in the sample."""
    for v in checks.values():
        if isinstance(v, dict) and v["value"] > v["limit"]:
            return False
    if mix["hit_share"] > 0 and not checks["hits_checked"]:
        return False
    if mix["hit_share"] < 1:
        return bool(checks["misses_checked"]) and "logit_gap" in checks
    return True
