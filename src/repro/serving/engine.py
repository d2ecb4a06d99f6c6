"""Serving engine: prefill + CHUNKED decode with inter-chunk cancellation.

The paper's Fig-2 "termination signal" cannot preempt a launched XLA
program, so decode runs as jit'd chunks of K tokens (one dispatch each);
between chunks the host checks cancellation (StorInfer's vector-search hit)
and the session stops paying for further compute within <= one chunk.
The same structure gives continuous batching its insertion points.

Components:
  Engine          — jit'd prefill / decode-chunk programs for one config
  Session         — single-request chunked generation with .cancel()
  BatchScheduler  — fixed-slot continuous batching over a shared cache;
                    per-slot cancellation == StorInfer hit-cancellation
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tokenizer import EOS
from repro.models import model as M

# Profiler spans of the decode worker (``jax.profiler.TraceAnnotation``;
# inert unless a profiler runs). Admission, the chunk and retirement tile
# ``BatchScheduler.step_chunk`` with the pipeline's wait span.
SPAN_ADMIT = "storinfer.decode.admit"
SPAN_PREFILL = "storinfer.decode.prefill"
SPAN_CHUNK = "storinfer.decode.chunk"
SPAN_FINISH = "storinfer.decode.finish"


def sample_token(logits, rng, temperature):
    lg = logits.astype(jnp.float32)
    if temperature is None:
        return jnp.argmax(lg, axis=-1)
    t = jnp.maximum(temperature, 1e-6)
    return jax.random.categorical(rng, lg / t, axis=-1)


# The engine's run: the residual stream, norms, softmax and logits in f32
# over params held at the configuration's dtype; each matmul takes a bf16
# weight as it is, with its activation in bf16, accumulating in f32. Expert
# layers drop no token, so a live slot's answer never depends on the others.
SERVE_RUN = M.RunCfg(attn_impl="naive", remat=False,
                     stream_dtype=jnp.float32, moe_impl="dropless")


def _experts_touched(cfg, routes, live):
    """Held experts that at least one live slot routed to, summed over the
    expert layers: routes (L_moe, B, K) global expert ids, live (B,)."""
    held = cfg.expert_offset + jnp.arange(cfg.experts_held)
    hit = (routes[..., None] == held) & live[None, :, None, None]
    return jnp.sum(jnp.any(hit, axis=(1, 2)).astype(jnp.int32))


class Engine:
    """One model, jit'd once; serves many sessions."""

    def __init__(self, cfg, params, tokenizer, run: M.RunCfg = None,
                 max_len: int = 256, chunk: int = 8):
        self.cfg = cfg
        self.params = params
        self.tok = tokenizer
        self.run = run or SERVE_RUN
        self.max_len = max_len
        self.chunk = chunk
        self._prefill = jax.jit(self._prefill_impl)
        self._decode_chunk = jax.jit(self._decode_chunk_impl)
        self._write_slot = jax.jit(self._write_slot_impl,
                                   donate_argnums=(0,))

    # -- jit bodies -----------------------------------------------------------
    def _prefill_impl(self, params, tokens):
        batch = {"tokens": tokens}
        logits, cache = M.prefill(self.cfg, params, batch, self.run,
                                  max_len=self.max_len)
        return logits, cache

    def _decode_chunk_impl(self, params, token, cache, cache_len, rng,
                           temperature, live):
        """Runs ``chunk`` decode steps. live: (B,) bool — dead slots decode
        and keep their token. Each step writes every slot's row at
        cache_len, dead slots' too: a dead slot's row is read by no live
        slot (attention never reads across slots, and the dropless expert
        layer lets no token reach another's answer), and admission
        rewrites a slot's whole ``max_len`` row (``_write_slot``) before
        the slot is read again. Returns (token, cache, cache_len, tokens
        (B, chunk)), and where the expert layers report their routes (the
        dropless serving layer) a fifth output, int32 (3,), summed over
        expert layers and the steps with a live slot: the held experts
        that at least one live slot routed to, the layer steps, and the
        held experts those layer steps read (all of them: the dropless
        layer reads every held expert)."""

        def body(carry, _):
            tok, cache, clen, rng = carry
            rng, sub = jax.random.split(rng)
            logits, new_cache, routes = M.decode_step(self.cfg, params, tok,
                                                      cache, clen, self.run)
            nxt = sample_token(logits[:, -1, :], sub, temperature)[:, None]
            nxt = nxt.astype(jnp.int32)
            keep = live[:, None]
            nxt = jnp.where(keep, nxt, tok)
            touched = None if routes is None else \
                _experts_touched(self.cfg, routes, live)
            return (nxt, new_cache, clen + 1, rng), (nxt[:, 0], touched)

        (tok, cache, clen, _), (toks, touched) = jax.lax.scan(
            body, (token, cache, cache_len, rng), None, length=self.chunk)
        out = (tok, cache, clen, jnp.transpose(toks))  # toks (B, chunk)
        if touched is None:
            return out
        n_moe = self.cfg.n_layers - self.cfg.first_dense_layers
        layer_steps = n_moe * self.chunk * jnp.any(live).astype(jnp.int32)
        return out + (jnp.stack([jnp.sum(touched), layer_steps,
                                 layer_steps * self.cfg.experts_held]),)

    def _write_slot_impl(self, batch_cache, one_cache, slot):
        """Insert a prefilled single-request cache at batch slot ``slot``."""

        def wr(bc, oc):
            return jax.lax.dynamic_update_slice(
                bc, oc.astype(bc.dtype),
                (0, slot) + (0,) * (bc.ndim - 2))

        return jax.tree_util.tree_map(wr, batch_cache, one_cache)

    # -- single-shot generation ------------------------------------------------
    def generate(self, prompt: str, max_new: int = 32, temperature=None,
                 seed: int = 0) -> str:
        s = self.start_session(prompt, max_new=max_new,
                               temperature=temperature, seed=seed)
        while not s.done:
            s.step_chunk()
        return s.text()


    def start_session(self, prompt: str, max_new: int = 32, temperature=None,
                      seed: int = 0) -> "Session":
        return Session(self, prompt, max_new, temperature, seed)

    # -- batch session API ----------------------------------------------------
    def start_batch_session(self, prompts, *, max_new=32, temperature=None,
                            batch_size: int = None) -> "BatchSession":
        return BatchSession(self, prompts, max_new=max_new,
                            temperature=temperature, batch_size=batch_size)

    def generate_batch(self, prompts, *, max_new=32, temperature=None,
                       batch_size: int = None) -> List[str]:
        s = self.start_batch_session(prompts, max_new=max_new,
                                     temperature=temperature,
                                     batch_size=batch_size)
        s.run()
        return [s.text(i) for i in range(s.n)]


class Session:
    """Single-request chunked generation with host-side cancellation."""

    def __init__(self, engine: Engine, prompt: str, max_new, temperature,
                 seed):
        self.e = engine
        ids = engine.tok.encode(prompt, bos=True)[: engine.max_len - 1]
        tokens = jnp.asarray([ids], jnp.int32)
        t0 = time.perf_counter()
        logits, cache = engine._prefill(engine.params, tokens)
        self.prefill_s = time.perf_counter() - t0
        self.cache = cache
        self.cache_len = jnp.asarray(len(ids) - 1, jnp.int32)
        self.token = jnp.asarray(
            [[int(jnp.argmax(logits[0, -1]))]], jnp.int32)
        self.out_ids: List[int] = [int(self.token[0, 0])]
        self.max_new = max_new
        self.temperature = temperature
        self.rng = jax.random.PRNGKey(seed)
        self.cancelled = False
        self.decode_s = 0.0
        self.chunks_run = 0

    @property
    def done(self) -> bool:
        return (self.cancelled or len(self.out_ids) >= self.max_new
                or (self.out_ids and self.out_ids[-1] == EOS))

    def cancel(self):
        """The paper's termination signal (takes effect between chunks)."""
        self.cancelled = True

    def step_chunk(self):
        if self.done:
            return
        t0 = time.perf_counter()
        self.rng, sub = jax.random.split(self.rng)
        live = jnp.ones((1,), bool)
        self.token, self.cache, self.cache_len, toks, *_ = \
            self.e._decode_chunk(self.e.params, self.token, self.cache,
                                 self.cache_len + 1, sub,
                                 self.temperature, live)
        self.cache_len = self.cache_len - 1
        toks = np.asarray(toks[0])
        for t in toks:
            if len(self.out_ids) >= self.max_new or t == EOS:
                break
            self.out_ids.append(int(t))
        self.decode_s += time.perf_counter() - t0
        self.chunks_run += 1

    def text(self) -> str:
        return self.e.tok.decode(self.out_ids)


# ---------------------------------------------------------------------------
# Continuous batching with per-slot (hit-)cancellation
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Request:
    rid: int
    prompt: str
    max_new: int = 32
    temperature: Optional[float] = None
    out_ids: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    cancelled: bool = False
    slot: int = -1
    t_submit: float = 0.0     # perf_counter stamp at BatchScheduler.submit
    t_done: float = 0.0       # perf_counter stamp when the slot retired
    chunks: int = 0           # decode chunks this request was live for


class BatchScheduler:
    """Fixed B slots over one shared batched cache; requests enter in
    equal-prompt-length waves (prefill -> slot write), leave on
    EOS/max/cancel. Cancellation is the StorInfer hit path: the slot is
    freed at the next chunk boundary.

    Admission is wave-gated: the cache keeps a single shared ``cache_len``,
    so a new prompt may only be admitted when no slot is mid-decode (a
    mid-flight admission would reset ``cache_len`` under the live slots)
    and every prompt admitted into one wave must tokenize to the same
    length. Mixed-length traffic simply forms multiple waves.

    The scheduler is built to be PERSISTENT: slots join and leave between
    waves (a freed slot — finished or hit-cancelled — is refilled from
    ``waiting`` as soon as the wave drains) rather than the whole batch
    being torn down per admission. ``ServingPipeline``'s decode stage
    keeps one instance alive across every microbatch and feeds misses in
    continuously; ``waves`` / ``admitted`` / ``slot_uses`` account for
    the reuse, ``slot_wait_s`` (submit to admission, summed over admitted
    requests) for the wait, and ``len_cuts`` for the waves closed by a
    prompt-length mismatch while a slot was still free. A model with expert
    layers counts ``experts_touched`` (held experts that a live slot routed
    to, summed over expert layers and decode steps), ``moe_layer_steps``
    (expert layers times decode steps with a live slot) and
    ``experts_read`` (the held experts those layer steps read)."""

    def __init__(self, engine: Engine, batch_size: int = 4):
        self.e = engine
        self.B = batch_size
        cfg = engine.cfg
        self.cache = M.init_cache(cfg, batch_size, engine.max_len)
        self.token = jnp.zeros((batch_size, 1), jnp.int32)
        self.live = np.zeros(batch_size, bool)
        self.reqs: List[Optional[Request]] = [None] * batch_size
        self.cache_len = jnp.asarray(0, jnp.int32)
        self.waiting: List[Request] = []
        self.finished: List[Request] = []
        self.rng = jax.random.PRNGKey(0)
        self.waves = 0                      # admission waves opened
        self.admitted = 0                   # requests given a slot, ever
        self.slot_uses = [0] * batch_size   # admissions per slot (reuse)
        self.slot_wait_s = 0.0              # submit -> admission, summed
        self.len_cuts = 0                   # waves closed on prompt length
        self.experts_touched = 0            # held experts live slots routed
        #                                     to, over expert layers x steps
        self.moe_layer_steps = 0            # expert layers x live steps
        self.experts_read = 0               # held experts those steps read

    def submit(self, req: Request):
        req.t_submit = time.perf_counter()
        self.waiting.append(req)

    @property
    def idle(self) -> bool:
        """Nothing decoding and nothing waiting for a slot."""
        return not self.live.any() and not self.waiting

    def drain_finished(self) -> List[Request]:
        """Pop and return everything finished since the last drain (the
        persistent-loop accessor; ``BatchSession.results`` reads the
        accumulating ``finished`` list instead)."""
        done, self.finished = self.finished, []
        return done

    def cancel(self, rid: int):
        for r in self.reqs:
            if r is not None and r.rid == rid:
                r.cancelled = True
        for r in self.waiting:
            if r.rid == rid:
                r.cancelled = True

    def _admit(self):
        if self.live.any() or not self.waiting:
            return          # wave in flight; next wave starts once it drains
        with jax.profiler.TraceAnnotation(SPAN_ADMIT):
            self._admit_wave()

    def _admit_wave(self):
        wave_len = None
        wave_temp = _UNSET = object()
        free = list(range(self.B))
        while free and self.waiting:
            req = self.waiting[0]
            if req.cancelled:
                self.waiting.pop(0)
                req.done = True
                req.t_done = time.perf_counter()
                self.finished.append(req)
                continue
            ids = self.e.tok.encode(req.prompt, bos=True)
            ids = ids[: self.e.max_len - req.max_new - 1]
            if wave_len is not None and len(ids) != wave_len:
                self.len_cuts += 1
                break       # different prompt length -> opens the next wave
            if wave_temp is not _UNSET and req.temperature != wave_temp:
                break       # decode runs ONE temperature per chunk, so a
            #                 wave admits only same-temperature requests
            #                 (mixed traffic forms waves, like lengths)
            self.waiting.pop(0)
            self.slot_wait_s += time.perf_counter() - req.t_submit
            wave_temp = req.temperature
            if wave_len is None:
                self.waves += 1
            wave_len = len(ids)
            slot = free.pop(0)
            self.admitted += 1
            self.slot_uses[slot] += 1
            with jax.profiler.TraceAnnotation(SPAN_PREFILL):
                tokens = jnp.asarray([ids], jnp.int32)
                logits, one_cache = self.e._prefill(self.e.params, tokens)
                self.cache = self.e._write_slot(
                    self.cache, one_cache, jnp.asarray(slot, jnp.int32))
                first = int(jnp.argmax(logits[0, -1]))
            req.out_ids.append(first)
            req.slot = slot
            self.token = self.token.at[slot, 0].set(first)
            self.live[slot] = True
            self.reqs[slot] = req
            self.cache_len = jnp.asarray(wave_len - 1, jnp.int32)

    def _retire(self):
        with jax.profiler.TraceAnnotation(SPAN_FINISH):
            for slot in range(self.B):
                r = self.reqs[slot]
                if r is None:
                    continue
                if (r.cancelled or len(r.out_ids) >= r.max_new
                        or (r.out_ids and r.out_ids[-1] == EOS)):
                    r.done = True
                    r.t_done = time.perf_counter()
                    self.finished.append(r)
                    self.reqs[slot] = None
                    self.live[slot] = False

    def step_chunk(self):
        self._admit()
        self._retire()
        if not self.live.any():
            return False
        with jax.profiler.TraceAnnotation(SPAN_CHUNK):
            self.rng, sub = jax.random.split(self.rng)
            temps = [r.temperature for r in self.reqs if r is not None]
            temp = temps[0] if temps and temps[0] is not None else None
            self.token, self.cache, self.cache_len, toks, *experts = \
                self.e._decode_chunk(self.e.params, self.token, self.cache,
                                     self.cache_len + 1, sub, temp,
                                     jnp.asarray(self.live))
            self.cache_len = self.cache_len - 1
            toks = np.asarray(toks)
            if experts:
                touched, layer_steps, read = np.asarray(experts[0]).tolist()
                self.experts_touched += touched
                self.moe_layer_steps += layer_steps
                self.experts_read += read
            for slot in range(self.B):
                r = self.reqs[slot]
                if r is None:
                    continue
                r.chunks += 1
                for t in toks[slot]:
                    if len(r.out_ids) >= r.max_new or t == EOS:
                        break
                    r.out_ids.append(int(t))
        self._retire()
        return True

    def run_to_completion(self, max_chunks=1000):
        for _ in range(max_chunks):
            self._admit()
            if not self.step_chunk() and not self.waiting:
                break
        return self.finished


# ---------------------------------------------------------------------------
# Batch session API (used by core.runtime.BatchedRuntime)
# ---------------------------------------------------------------------------


class BatchSession:
    """A batch of prompts decoded together with per-request cancellation —
    the batched analogue of ``Session``. ``cancel(i)`` is the StorInfer
    termination signal for prompt ``i``; it takes effect at the next chunk
    boundary (or before prefill if the request is still waiting)."""

    def __init__(self, engine: Engine, prompts: Sequence[str], *,
                 max_new=32, temperature=None, batch_size: int = None):
        self.n = len(prompts)
        slots = min(self.n, batch_size) if batch_size else self.n
        self.sched = BatchScheduler(engine, batch_size=max(slots, 1))
        per_req_max = (list(max_new) if isinstance(max_new, (list, tuple))
                       else [max_new] * self.n)
        self.reqs = [Request(rid=i, prompt=p, max_new=per_req_max[i],
                             temperature=temperature)
                     for i, p in enumerate(prompts)]
        for r in self.reqs:
            self.sched.submit(r)
        self.decode_s = 0.0
        self.chunks_run = 0

    @property
    def done(self) -> bool:
        return len(self.sched.finished) >= self.n

    def cancel(self, i: int):
        self.sched.cancel(i)

    def step_chunk(self):
        if self.done:
            return
        t0 = time.perf_counter()
        self.sched._admit()
        if self.sched.step_chunk():
            self.chunks_run += 1
        self.decode_s += time.perf_counter() - t0

    def run(self, max_chunks: int = 10000) -> List[Request]:
        for _ in range(max_chunks):
            if self.done:
                break
            self.step_chunk()
        return self.results()

    def results(self) -> List[Request]:
        return sorted(self.sched.finished, key=lambda r: r.rid)

    def text(self, i: int) -> str:
        return self.sched.e.tok.decode(self.reqs[i].out_ids)


