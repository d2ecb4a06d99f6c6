"""Load drivers: an open loop on a fixed schedule and a closed loop of
clients, both from the harness's one thread, through ``StorInfer.submit``.

Each request's latency runs from when it was due (the scheduled send time
in the open loop, the send in the closed loop) to when its future resolved,
so a stall also charges the requests queued behind it. The open loop
records how late each send was.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from typing import List, Optional

import jax


@dataclasses.dataclass
class Record:
    req: object               # traffic.Request
    due: float                # perf_counter when it was due
    sent: float = 0.0
    done: float = 0.0         # perf_counter when its future resolved
    result: object = None    # QueryResult
    error: Optional[str] = None


def latency_ms(rec: Record, deadline: float) -> float:
    """From when the request was due to when its future resolved; a failed
    or unanswered request counts at the drain ``deadline``."""
    ok = rec.done and rec.error is None
    return ((rec.done if ok else deadline) - rec.due) * 1e3


def served_kind(rec: Record) -> str:
    """How the system answered the request, ``"hit"`` or ``"miss"``; as
    planned where it gave no answer."""
    if rec.done and rec.error is None:
        return "hit" if rec.result.hit else "miss"
    return rec.req.kind


def _attach(rec: Record, fut, on_done=None):
    def cb(f):
        t = time.perf_counter()
        try:
            rec.result = f.result()
        except BaseException as e:          # noqa: BLE001 — recorded
            rec.error = f"{type(e).__name__}: {e}"
        rec.done = t
        if on_done is not None:
            on_done(rec)
    fut.add_done_callback(cb)


def _submit(si, rec: Record, on_done=None):
    rec.sent = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.submit"):
        try:
            fut = si.submit(rec.req.text, max_new=rec.req.max_new)
        except Exception as e:              # noqa: BLE001 — a refused send
            rec.done, rec.error = rec.sent, f"{type(e).__name__}: {e}"
            if on_done is not None:
                on_done(rec)
            return
    _attach(rec, fut, on_done)


def open_loop(si, reqs, times, t0: float) -> List[Record]:
    """Send ``reqs[i]`` at ``t0 + times[i]``; returns the records."""
    recs = []
    for req, t in zip(reqs, times):
        rec = Record(req, due=t0 + float(t))
        wait = rec.due - time.perf_counter()
        if wait > 0:
            with jax.profiler.TraceAnnotation("bench.wait_schedule"):
                time.sleep(wait)
        _submit(si, rec)
        recs.append(rec)
    return recs


def closed_loop(si, reqs, clients: int, t_end: float) -> List[Record]:
    """``clients`` clients, each sending its next request as soon as its
    last one resolved, until ``t_end``."""
    done_q: "queue.Queue[Record]" = queue.Queue()
    it = iter(reqs)
    recs = []

    def send():
        try:
            req = next(it)
        except StopIteration:
            raise RuntimeError("closed loop ran out of planned requests; "
                               "raise the mix's max_rate_per_s") from None
        rec = Record(req, due=time.perf_counter())
        recs.append(rec)
        _submit(si, rec, done_q.put)

    for _ in range(clients):
        send()
    while True:
        left = t_end - time.perf_counter()
        if left <= 0:
            break
        try:
            with jax.profiler.TraceAnnotation("bench.wait_reply"):
                done_q.get(timeout=left)
        except queue.Empty:
            break
        if time.perf_counter() < t_end:
            send()
    return recs


def drain(recs: List[Record], deadline: float) -> int:
    """Wait until every record resolved or ``deadline``; returns how many
    are still open."""
    while time.perf_counter() < deadline:
        if all(r.done for r in recs):
            return 0
        time.sleep(0.05)
    return sum(1 for r in recs if not r.done)
