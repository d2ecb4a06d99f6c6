"""``correct`` has to fail when the timed path is broken under the
harness, and when the float8 control stands in for the program, whose
own readings stay below the limit. A tiny cell on the CPU, the rest of a
run as on the chip."""
from __future__ import annotations

import pytest

from conftest import TINY_CFG, run_cell


def _alter_served_tokens(si):
    """A token altered where it is produced: the decode chunk's sampled
    tokens are shifted by one before the scheduler reads them."""
    eng = si.engine
    orig = eng._decode_chunk
    vocab = eng.cfg.vocab_size

    def broken(*args):
        tok, cache, clen, toks = orig(*args)
        return tok, cache, clen, (toks + 1) % vocab
    eng._decode_chunk = broken


def _alter_hit_answers(si):
    """An answer altered where it is produced: the resolve stage reads the
    pair of the next row."""
    orig = si.store.get_pair
    si.store.get_pair = lambda row: orig(row + 1)


def _decode_state_unchanged(si):
    """A step that returns its state unchanged: the decode chunk hands
    back the cache it was given, so no served token is written to it."""
    eng = si.engine
    orig = eng._decode_chunk

    def broken(params, token, cache, *rest):
        tok, _, clen, toks = orig(params, token, cache, *rest)
        return tok, cache, clen, toks
    eng._decode_chunk = broken


def _half_of_each_search_batch(si):
    """Half of the batch left out: each search scores only the first half
    of its queries and hands their rows to the rest as well."""
    orig = si.index.search

    def broken(queries, k):
        n = len(queries)
        if n < 2:
            return orig(queries, k)
        v, i = orig(queries[: n // 2], k)
        pick = [j % (n // 2) for j in range(n)]
        return v[pick], i[pick]
    si.index.search = broken


def test_decode_state_left_unchanged_fails_correct(tiny_root, no_cache):
    res = run_cell(tiny_root, "tiny.t1k.tnovel", 13,
                   hook=_decode_state_unchanged)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_half_of_each_search_batch_left_out_fails_correct(tiny_root,
                                                          no_cache):
    res = run_cell(tiny_root, "tiny.t1k.tnovel", 14,
                   hook=_half_of_each_search_batch)
    assert res["correct"] is False
    wrong = res["checks"]["hit_wrong"]["value"] + \
        res["checks"]["route_wrong"]["value"]
    assert wrong > 0


def test_altered_tokens_fail_correct(tiny_root, no_cache):
    res = run_cell(tiny_root, "tiny.t1k.tnovel", 11,
                   hook=_alter_served_tokens)
    assert res["correct"] is False
    gap = res["checks"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_altered_answers_fail_correct(tiny_root, no_cache):
    res = run_cell(tiny_root, "tiny.t1k.tfaq", 12, hook=_alter_hit_answers)
    assert res["correct"] is False
    assert res["checks"]["hit_wrong"]["value"] > 0


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_float8_control_reads_above_the_limit(tiny_root, no_cache, seed):
    """The control in the program's place, through the same comparison:
    ``correct`` comes out false, while the program's own reading on the
    same run stays within the limit."""
    res = run_cell(tiny_root, "tiny.t1k.tfaq", seed, control=True)
    limit = TINY_CFG["limits"]["logit_gap"]
    assert res["correct"] is False
    assert res["checks"]["logit_gap"]["value"] > limit
    assert res["checks"]["logit_gap"]["limit"] == limit
    assert res["program_logit_gap"] <= limit
    assert res["failed"] == 0
