"""One general generator for every traffic mix.

A mix file gives parameters only: the loop (``open`` with a fixed Poisson
rate, or ``closed`` with a number of clients), the share of hits and the
sample size the schedule's lengths are drawn from. What users ask, and how
they phrase it, is the configuration's ``users`` model: a fact of the
knowledge base chosen by its popularity (Zipf over the KB's ranks), then a
phrasing: one of the templates the offline build anticipates, with a
greeting or none, or one it does not (``hard_templates``). The same model
renders the stored pairs (``stored_pairs``: anticipated phrasings only,
each answered with its fact's statement), so user questions land at every
score against the store: verbatim repeats, greetings that move a stored
question just across the hit threshold or just short of it, other
phrasings far below.

Every seed gets the same work. The schedule is one fixed sample drawn from
the mix's own ``schedule_seed``: which requests are hits (a balanced set
shuffled within blocks of ``BLOCK`` requests, so any stretch holds nearly
the mix's share), each miss's prompt length in tokens (quantiles of the
lengths of the users' own missing questions), each request's answer budget
(quantiles of the token lengths of the knowledge base's answers) and the
Poisson gaps (exponential quantiles at the mix's rate), the last three in
shuffled orders. The run's seed draws what the requests say: questions
from the users model, kept where their class (hit or miss) and, for a
miss, their length match the slot. Runs with different seeds then differ
by their texts and weights, not by how much work arrives when.
"""
from __future__ import annotations

import dataclasses
import math
from collections import defaultdict
from typing import Callable, List, Optional, Sequence

import numpy as np

BLOCK = 20
DRAW = 512                  # questions drawn per round while filling a plan
MAX_ROUNDS = 400


@dataclasses.dataclass
class Request:
    kind: str                 # "hit" | "miss": what the plan meant it to be
    text: str
    max_new: int
    prompt_len: int = 0       # tokens including BOS


@dataclasses.dataclass(frozen=True)
class Fact:
    entity: str
    relation: str
    value: str
    rank: int                 # popularity rank among the KB's facts

    def answer(self) -> str:
        return f"the {self.relation} of {self.entity} is {self.value}."


class Users:
    """The configuration's users: Zipf(``zipf_a``) over the facts' ranks,
    then a hard phrasing with probability ``hard_frac``, else a template
    (rank-skewed by ``template_skew``) after a uniformly chosen filler."""

    def __init__(self, model: dict, facts: Sequence[Fact]):
        self.facts = list(facts)
        self.templates = model["templates"]
        self.hard = model["hard_templates"]
        self.fillers = model["fillers"]
        self.hard_frac = model["hard_frac"]
        ranks = np.asarray([f.rank for f in self.facts], np.float64)
        p = (ranks + 1.0) ** -model["zipf_a"]
        self.p_fact = p / p.sum()
        t = np.arange(1, len(self.templates) + 1, dtype=np.float64) \
            ** -model["template_skew"]
        self.p_tmpl = t / t.sum()

    def ask(self, rng, n: int, hard: bool = True) -> List[tuple]:
        """``n`` (question, fact) pairs."""
        fi = rng.choice(len(self.facts), n, p=self.p_fact)
        is_hard = (rng.random(n) < self.hard_frac) if hard else np.zeros(n)
        hi = rng.integers(0, len(self.hard), n)
        ti = rng.choice(len(self.templates), n, p=self.p_tmpl)
        fl = rng.integers(0, len(self.fillers), n)
        out = []
        for i in range(n):
            f = self.facts[fi[i]]
            if is_hard[i]:
                q = self.hard[hi[i]].format(r=f.relation, e=f.entity)
            else:
                q = self.fillers[fl[i]] + self.templates[ti[i]].format(
                    r=f.relation, e=f.entity)
            out.append((q, f))
        return out


def stored_pairs(users: Users, seed: int, n: int) -> List[tuple]:
    """The store's ``n`` (question, answer) pairs: distinct anticipated
    phrasings in the order the users model first draws them from
    ``seed``, each answered with its fact's statement."""
    rng = np.random.default_rng(seed)
    pairs: dict = {}
    while len(pairs) < n:
        for q, f in users.ask(rng, DRAW, hard=False):
            if q not in pairs and len(pairs) < n:
                pairs[q] = f.answer()
    return list(pairs.items())


def _quantiles(pool: Sequence[int], n: int) -> np.ndarray:
    """``n`` values spread over the sorted ``pool`` at its (i + 0.5) / n
    quantiles: the same balanced set for every n-sized schedule."""
    pool = np.sort(np.asarray(pool))
    return pool[((np.arange(n) + 0.5) / n * len(pool)).astype(int)]


def _block_shuffle(n: int, rng) -> np.ndarray:
    perm = np.arange(n)
    for lo in range(0, n, BLOCK):
        perm[lo:lo + BLOCK] = lo + rng.permutation(min(BLOCK, n - lo))
    return perm


@dataclasses.dataclass
class Schedule:
    is_hit: np.ndarray
    prompt_len: np.ndarray    # per request; 0 for hits
    max_new: np.ndarray
    gaps: np.ndarray          # in units of the mean gap


def schedule(mix: dict, n: int, users: Users, is_hit: Callable,
             token_len: Callable, answer_lens: Sequence[int]) -> Schedule:
    """The fixed schedule of ``n`` requests. Miss lengths are quantiles of
    the token lengths of the missing questions among ``length_sample``
    the users model draws from the mix's ``schedule_seed``."""
    rng = np.random.default_rng(mix["schedule_seed"])
    share = mix["hit_share"]
    idx = np.arange(n)
    hit = np.floor((idx + 1) * share) > np.floor(idx * share)
    hit = hit[_block_shuffle(n, rng)]
    sample = [q for q, _ in users.ask(rng, mix["length_sample"])]
    miss_pool = [token_len(q) for q, h in zip(sample, is_hit(sample))
                 if not h]
    n_miss = int((~hit).sum())
    lens = np.zeros(n, int)
    lens[~hit] = _quantiles(miss_pool, n_miss)[rng.permutation(n_miss)]
    budgets = _quantiles(answer_lens, n)[rng.permutation(n)]
    gaps = -np.log1p(-(idx + 0.5) / n)                # Exp(1) quantiles
    return Schedule(hit, lens, budgets, gaps[rng.permutation(n)])


def plan(sched: Schedule, seed: int, users: Users, is_hit: Callable,
         token_len: Callable) -> List[Request]:
    """The run's requests: the schedule's slots filled in order with
    questions the users model draws from ``seed``, each where its class
    and (for a miss) its token length match."""
    rng = np.random.default_rng(seed)
    n = len(sched.is_hit)
    texts: List[Optional[str]] = [None] * n
    hit_slots = [i for i in range(n) if sched.is_hit[i]]
    miss_slots = defaultdict(list)
    for i in range(n):
        if not sched.is_hit[i]:
            miss_slots[int(sched.prompt_len[i])].append(i)
    for b in miss_slots.values():
        b.reverse()
    hit_slots.reverse()
    left = n
    for _ in range(MAX_ROUNDS):
        if not left:
            break
        qs = [q for q, _ in users.ask(rng, DRAW)]
        for q, h in zip(qs, is_hit(qs)):
            slots = hit_slots if h else miss_slots.get(token_len(q))
            if slots:
                texts[slots.pop()] = q
                left -= 1
    if left:
        raise RuntimeError(f"{left} slots found no question of their class "
                           f"and length in {MAX_ROUNDS * DRAW} draws")
    return [Request("hit" if sched.is_hit[i] else "miss", texts[i],
                    int(sched.max_new[i]), int(sched.prompt_len[i]))
            for i in range(n)]


def arrivals(mix: dict, sched: Schedule, seconds: float) -> np.ndarray:
    """Open loop: the send times of the fixed schedule, in (0, seconds) at
    the mix's rate."""
    gaps = sched.gaps
    t = np.cumsum(gaps) / mix["rate_per_s"]
    return t * (seconds / (t[-1] + gaps.mean() / mix["rate_per_s"]))


def n_requests(mix: dict, seconds: float) -> int:
    """How many requests a run's plan holds: the open loop's due count, or
    enough for a closed loop's clients at the mix's stated ceiling."""
    if mix["loop"] == "open":
        return max(1, int(round(mix["rate_per_s"] * seconds)))
    return mix["clients"] + int(math.ceil(mix["max_rate_per_s"] * seconds))


def pick(seed: int, items: list, k: int, must: Optional[list] = None):
    """A sample of ``k`` of ``items`` drawn from the seed, with ``must``."""
    rng = np.random.default_rng(seed + 2)
    must = list(must or [])
    rest = [x for x in items if all(x is not m for m in must)]
    k = max(0, min(k - len(must), len(rest)))
    idx = rng.choice(len(rest), k, replace=False) if k else []
    return must + [rest[i] for i in sorted(idx)]
