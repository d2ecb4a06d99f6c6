"""Query embedders.

Two implementations behind one ``encode(texts) -> (n, dim) L2-normalized``
interface:

* ``HashEmbedder`` — signed n-gram feature hashing (the "hashing trick").
  Deterministic, no training, lexically semantic: paraphrases sharing
  content words land close in cosine space. This is the default for the
  paper-reproduction benchmarks (plays the role of all-MiniLM-L6-v2, whose
  weights don't ship in this container).

* ``MiniLMEncoder`` — an all-MiniLM-class (6L, 384d) JAX transformer
  encoder with mean pooling, plus an InfoNCE contrastive trainer over
  synthetic paraphrase pairs — the full neural path, used by tests/examples
  to prove the system runs a real JAX encoder end-to-end.

MIPS on L2-normalized embeddings == cosine similarity (the paper's metric).
"""
from __future__ import annotations

import dataclasses
import re
import zlib
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as Lyr
from repro.models import model as M

_WORDS = re.compile(r"\w+")


class HashEmbedder:
    def __init__(self, dim: int = 384, ngrams=(1, 2), seed: int = 0):
        self.dim = dim
        self.ngrams = ngrams
        self.seed = seed

    def _features(self, text: str):
        ws = _WORDS.findall(text.lower())
        feats = []
        for n in self.ngrams:
            for i in range(len(ws) - n + 1):
                feats.append(" ".join(ws[i:i + n]))
        return feats

    def encode(self, texts: List[str]) -> np.ndarray:
        out = np.zeros((len(texts), self.dim), np.float32)
        for i, t in enumerate(texts):
            for f in self._features(t):
                h = zlib.crc32((f + f"#{self.seed}").encode())
                idx = h % self.dim
                sign = 1.0 if (h >> 17) & 1 else -1.0
                out[i, idx] += sign
        norms = np.linalg.norm(out, axis=1, keepdims=True)
        return out / np.maximum(norms, 1e-9)


# ---------------------------------------------------------------------------
# MiniLM-class JAX encoder
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EncoderCfg:
    vocab_size: int
    dim: int = 384
    n_layers: int = 6
    n_heads: int = 6
    d_ff: int = 1536
    max_len: int = 64


def _enc_model_cfg(cfg: EncoderCfg):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name="minilm-enc", family="dense", n_layers=cfg.n_layers,
        d_model=cfg.dim, n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
        d_ff=cfg.d_ff, vocab_size=cfg.vocab_size,
        head_dim=cfg.dim // cfg.n_heads, gated_mlp=False, mlp_act="gelu",
        rope_kind="none", dtype="float32")


class MiniLMEncoder:
    """Mean-pooled transformer encoder; ``encode`` batches + L2-normalizes."""

    def __init__(self, tokenizer, cfg: EncoderCfg = None, seed: int = 0,
                 max_batch: int = 256):
        self.tok = tokenizer
        self.cfg = cfg or EncoderCfg(vocab_size=tokenizer.vocab_size)
        self.dim = self.cfg.dim
        self.max_batch = max_batch
        self.mcfg = _enc_model_cfg(self.cfg)
        key = jax.random.PRNGKey(seed)
        self.params = self._init(key)
        self._fwd = jax.jit(self._forward)

    def _init(self, key):
        ks = jax.random.split(key, 3)
        return {
            "embed": {"w": (jax.random.normal(
                ks[0], (self.cfg.vocab_size, self.cfg.dim), jnp.float32)
                * self.cfg.dim ** -0.5)},
            "blocks": M._stack_init(ks[1], self.mcfg, "enc",
                                    self.cfg.n_layers, jnp.float32),
            "final_norm": Lyr.rmsnorm_init(self.cfg.dim, jnp.float32),
        }

    def _forward(self, params, tokens, mask):
        """tokens (B, L) int32; mask (B, L) f32. Returns (B, dim) L2-normed."""
        x = jnp.take(params["embed"]["w"], tokens, axis=0)
        x = x + Lyr.sinusoidal_positions(tokens.shape[1],
                                         self.cfg.dim)[None]
        pos = jnp.broadcast_to(jnp.arange(tokens.shape[1], dtype=jnp.int32),
                               tokens.shape)
        run = M.RunCfg(attn_impl="naive", remat=False)
        x, _, _ = M._scan_stack(self.mcfg, run, params["blocks"], x, pos,
                                kind="enc", build_cache=False)
        x = Lyr.rmsnorm(params["final_norm"], x, 1e-6)
        pooled = (x * mask[..., None]).sum(1) / jnp.maximum(
            mask.sum(1, keepdims=True), 1.0)
        return pooled / jnp.maximum(
            jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)

    def _batch(self, texts, pad_to: int = 0):
        L = self.cfg.max_len
        rows = max(len(texts), pad_to)
        toks = np.zeros((rows, L), np.int32)
        mask = np.zeros((rows, L), np.float32)
        for i, t in enumerate(texts):
            ids = self.tok.encode(t)[:L]
            toks[i, :len(ids)] = ids
            mask[i, :len(ids)] = 1.0
        return jnp.asarray(toks), jnp.asarray(mask)

    @staticmethod
    def _bucket(n: int) -> int:
        """Next power of two: one jit compilation per bucket instead of one
        per distinct batch size (precompute waves and serving microbatches
        arrive in many sizes)."""
        b = 1
        while b < n:
            b *= 2
        return b

    def encode(self, texts: List[str]) -> np.ndarray:
        """Batched + L2-normalized. Batches are padded to power-of-two
        buckets (padding rows carry an all-zero mask and are sliced off)
        and chunked at ``max_batch`` so arbitrarily large precompute waves
        neither recompile nor blow device memory."""
        if not texts:
            return np.zeros((0, self.dim), np.float32)
        out = []
        for lo in range(0, len(texts), self.max_batch):
            chunk = texts[lo:lo + self.max_batch]
            toks, mask = self._batch(chunk, pad_to=self._bucket(len(chunk)))
            out.append(np.asarray(
                self._fwd(self.params, toks, mask))[:len(chunk)])
        return out[0] if len(out) == 1 else np.concatenate(out, axis=0)

    # -- contrastive training (InfoNCE over paraphrase pairs) --------------
    def train_contrastive(self, pairs, *, steps=200, bs=32, lr=1e-3,
                          temp=0.07, seed=0):
        """pairs: list of (text_a, text_b) positives. In-batch negatives."""
        rng = np.random.default_rng(seed)

        def loss_fn(params, ta, ma, tb, mb):
            za = self._forward(params, ta, ma)
            zb = self._forward(params, tb, mb)
            logits = za @ zb.T / temp
            labels = jnp.arange(za.shape[0])
            ll = jax.nn.log_softmax(logits, axis=-1)
            lt = jax.nn.log_softmax(logits.T, axis=-1)
            return -(ll[labels, labels].mean() + lt[labels, labels].mean())

        @jax.jit
        def step(params, opt, ta, ma, tb, mb):
            loss, g = jax.value_and_grad(loss_fn)(params, ta, ma, tb, mb)
            new_p, new_o = {}, {}
            flat_p, tdef = jax.tree_util.tree_flatten(params)
            flat_g = tdef.flatten_up_to(g)
            flat_m = tdef.flatten_up_to(opt)
            outp, outm = [], []
            for p, gg, m in zip(flat_p, flat_g, flat_m):
                m = 0.9 * m + 0.1 * gg
                outp.append(p - lr * m)
                outm.append(m)
            return (jax.tree_util.tree_unflatten(tdef, outp),
                    jax.tree_util.tree_unflatten(tdef, outm), loss)

        opt = jax.tree_util.tree_map(jnp.zeros_like, self.params)
        losses = []
        for s in range(steps):
            idx = rng.choice(len(pairs), size=min(bs, len(pairs)),
                             replace=False)
            ta, ma = self._batch([pairs[i][0] for i in idx])
            tb, mb = self._batch([pairs[i][1] for i in idx])
            self.params, opt, loss = step(self.params, opt, ta, ma, tb, mb)
            losses.append(float(loss))
        return losses
