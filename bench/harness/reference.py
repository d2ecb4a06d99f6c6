"""The plain reference the benchmark judges the served outputs by.

It imports nothing of the program. Everything it needs is written out here
in straightforward numpy and ``jax.numpy``:

* the hash embedder and the symmetric per-row int8 quantizer the
  configuration's store is defined by, and an exact int8 MIPS scan;
* the pieces the architecture modules (``bench/arch/<model_type>.py``)
  build their model from: seeded normal weights, RMSNorm, RoPE, and the
  linear layer with its control, every operand cast to float8 (e4m3,
  per-row and per-column scales), the step below the stated bfloat16
  that a later change might take;
* ``served_gaps``, which runs an architecture's float32 forward at
  ``highest`` matmul precision over the served sequences.

The model itself, with its weights made from the run's seed by the
published initialisation so that the reference holds its own copy, is
the architecture module's (``spec.load_arch``).
"""
from __future__ import annotations

import re
import zlib
from collections import Counter
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

_WORDS = re.compile(r"\w+")
_SPLIT = re.compile(r"\w+|[^\w\s]")
BOS, EOS = 1, 2
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


# ---------------------------------------------------------------------------
# search: hash embedding, int8 rows, exact scan
# ---------------------------------------------------------------------------


def hash_embed(texts, dim: int, ngrams=(1, 2), seed: int = 0) -> np.ndarray:
    """Signed n-gram feature hashing, L2-normalised (n, dim) float32."""
    out = np.zeros((len(texts), dim), np.float32)
    for i, t in enumerate(texts):
        ws = _WORDS.findall(t.lower())
        for n in ngrams:
            for j in range(len(ws) - n + 1):
                h = zlib.crc32((" ".join(ws[j:j + n]) + f"#{seed}").encode())
                out[i, h % dim] += 1.0 if (h >> 17) & 1 else -1.0
    norms = np.linalg.norm(out, axis=1, keepdims=True)
    return out / np.maximum(norms, 1e-9)


def quantize(rows: np.ndarray):
    """Symmetric per-row int8: scale = max|row| / 127, values rounded."""
    rows = np.asarray(rows, np.float32)
    amax = np.abs(rows).max(axis=1)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    vals = np.clip(np.rint(rows / scale[:, None]), -127, 127)
    return vals.astype(np.int8), scale


def scan_scores(q8, qs, x8, xs, block: int = 32768) -> np.ndarray:
    """Exact scores (Q, N) of int8 queries against int8 rows. The int32
    products are summed in float32, which is exact here (|sum| <= 127^2 D
    < 2^24), then scaled by the query's and the row's scale in that order."""
    qf = q8.astype(np.float32)
    out = np.empty((len(q8), len(x8)), np.float32)
    for lo in range(0, len(x8), block):
        acc = qf @ x8[lo:lo + block].astype(np.float32).T
        out[:, lo:lo + block] = acc * qs[:, None] * xs[None, lo:lo + block]
    return out


def vocab_ids(texts, max_vocab: int = 8192) -> dict:
    """Word -> token id of the configuration's word-level vocabulary: the
    corpus's lower-cased words and punctuation by frequency, after 4
    special ids and 256 byte ids."""
    counts = Counter()
    for t in texts:
        counts.update(w.lower() for w in _SPLIT.findall(t))
    return {w: 260 + i for i, (w, _) in
            enumerate(counts.most_common(max_vocab))}


def encode(text: str, vocab: dict) -> list:
    """BOS and the text's token ids under ``vocab``; a word outside it
    falls back to its UTF-8 bytes (ids 4-259)."""
    ids = [BOS]
    for w in _SPLIT.findall(text.lower()):
        wid = vocab.get(w)
        ids.extend([wid] if wid is not None else
                   [4 + b for b in w.encode("utf-8")])
    return ids


# ---------------------------------------------------------------------------
# model: what the architecture modules share
# ---------------------------------------------------------------------------


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 64) * 64


def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def _q8(x, axis):
    """x rounded to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _linear(x, w, fp8):
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return x @ w


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    """Rotate-half RoPE; x (B, S, H, D), pos (S,)."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, D, 2, dtype=np.float32) / D))
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@partial(jax.jit, static_argnums=(0, 1, 4))
def _logits(forward, cfg_items, w, tokens, fp8):
    return forward(dict(cfg_items), w, tokens, fp8)


def _frozen(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))
                        or v is None))


def served_gaps(cfg: dict, forward, w: dict, seqs, pad_to: int, *,
                control: bool = False, batch: int = 8):
    """For each (prompt ids, served ids) pair, under the architecture's
    ``forward(cfg, w, tokens, fp8)``: at every served position the
    gap by which the served token's reference logit lies below the
    reference's best and, with ``control``, the same gap for the token the
    float8 control puts first. Returns (program gaps, control gaps or
    None), one array per sequence.

    Sequences are right-padded to ``pad_to`` positions (or to the longest,
    if one is longer), so a run's calls compile once; padding sits after
    the causal positions it cannot affect."""
    items = _frozen(cfg)
    pad_to = max([pad_to] + [len(p) + len(v) - 1 for p, v in seqs])
    prog, ctrl = [], ([] if control else None)
    with jax.default_matmul_precision("highest"):
        for lo in range(0, len(seqs), batch):
            chunk = seqs[lo:lo + batch]
            toks = np.zeros((batch, pad_to), np.int32)
            for i, (prompt, served) in enumerate(chunk):
                ids = list(prompt) + list(served[:-1])
                toks[i, :len(ids)] = ids
            ref = np.asarray(_logits(forward, items, w, jnp.asarray(toks),
                                     False))
            if control:
                low = np.asarray(_logits(forward, items, w,
                                         jnp.asarray(toks), True))
            for i, (prompt, served) in enumerate(chunk):
                at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
                r = ref[i, at]
                best = r.max(-1)
                prog.append(best - r[np.arange(len(at)), served])
                if control:
                        ctrl.append(best - r[np.arange(len(at)),
                                         low[i, at].argmax(-1)])
    return prog, ctrl
