"""Arithmetic the per-layer metric readers share: latency percentiles,
counter deltas over the window, the window's finished misses, and device
time per decode step.

A reader gets ``ctx`` with the run's configuration (``cfg``), its
architecture module (``arch``, which counts the model's work), traffic mix
(``mix``), request records (``recs``), window bounds (``t0``, ``t_end``,
``window_s``), the drain deadline (``deadline``), the pipeline counters
before and after the window (``snap0``, ``snap1``), the reduced trace
(``trace``, traced runs only), the device's peaks (``peaks``) and the
store's row count (``store_rows``).
"""
from __future__ import annotations

import numpy as np

DECODE_PROGRAM = "decode_chunk"
SCAN_PROGRAM = "mips_topk_int8"


def stage_wait_ms(ctx, stage: str):
    """Mean wait of items entering ``stage`` during the window."""
    a, b = ctx.snap0["stages"][stage], ctx.snap1["stages"][stage]
    n = b["items"] - a["items"]
    if n <= 0:
        return None
    return (b["mean_wait_ms"] * b["items"] - a["mean_wait_ms"] * a["items"]) / n


def latency_pct_ms(ctx, kind: str, q: float):
    """The ``q``-th percentile latency of the requests due in the window
    that were served as ``kind``, counted as the end-to-end percentiles
    count them, or None when there were none."""
    from .drive import latency_ms, served_kind
    sel = [latency_ms(r, ctx.deadline) for r in ctx.recs
           if served_kind(r) == kind]
    if not sel:
        return None
    return float(np.percentile(np.asarray(sel, np.float64), q))


def searched(ctx) -> int:
    return (ctx.snap1["stages"]["search"]["items"]
            - ctx.snap0["stages"]["search"]["items"])


def search_batches(ctx) -> int:
    return ctx.snap1["search_batches"] - ctx.snap0["search_batches"]


def window_misses(ctx):
    """Misses that finished inside the window, with their prompt length."""
    return [r for r in ctx.recs
            if r.done and r.error is None and not r.result.hit
            and ctx.t0 <= r.done < ctx.t_end]


def token_positions(ctx):
    """0-based positions of every token the window's finished misses
    served (a prompt of L tokens puts its first served token at L)."""
    for r in window_misses(ctx):
        n0 = r.req.prompt_len
        for i in range(len(r.result.token_ids)):
            yield n0 + i


def model_flops(ctx) -> float:
    return float(sum(ctx.arch.flops_per_token(ctx.cfg, p)
                     for p in token_positions(ctx)))


def decode_steps(ctx):
    """(device seconds, decode steps) of the decode-chunk programs the
    trace holds, or None."""
    if ctx.trace is None:
        return None
    times = ctx.trace.module_times(DECODE_PROGRAM)
    if not times:
        return None
    return sum(times), len(times) * ctx.cfg["serving"]["chunk"]


def idle_percent(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s() / ctx.trace.window_s)
