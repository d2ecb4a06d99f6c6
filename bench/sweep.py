#!/usr/bin/env python3
"""Find the highest open-loop rate a cell's system sustains: one set-up,
then a window at each rate in turn.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates <r> [<r> ...]

For each rate, one JSON line: requests sent, misses sent and finished by
the window's close, the misses still open at the close (the backlog), and
the hit and miss latency percentiles from the scheduled send. A rate is
sustained while the backlog at the close stays at a few requests and the
miss tail does not grow with the window. The cell's rate is then fixed in
its traffic file at about 0.8 of the highest sustained rate (PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run
    from harness import drive, spec
    root = HERE.parent
    bench = spec.load_benchmark(root)
    cell = spec.load_cell(root, bench, args.workload)
    if cell["mix"]["loop"] != "open":
        sys.exit("sweep: the cell's traffic is not an open loop")
    sut = run.open_cell(root, cell, args.seed)
    cells = [dict(cell, mix=dict(cell["mix"], rate_per_s=rate))
             for rate in args.rates]
    plans = [run.build_traffic(c, args.seed + i, args.seconds, sut)
             for i, c in enumerate(cells)]
    with sut.si.serve():
        # every prompt length any rate's plan sends
        run.warm_up(sut.si, cell, [r for reqs, _ in plans for r in reqs],
                    [q for q, _ in sut.pairs])
        for rate, c, (reqs, times) in zip(args.rates, cells, plans):
            t0 = time.perf_counter() + 0.05
            t_end = t0 + args.seconds
            recs = drive.open_loop(sut.si, reqs, times, t0)
            time.sleep(max(0.0, t_end - time.perf_counter()))
            misses = [r for r in recs if r.req.kind == "miss"]
            open_at_close = sum(1 for r in misses if not r.done)
            drive.drain(recs, t_end + c["mix"]["drain_s"])
            out = run.end_to_end(
                [{"name": n, "unit": "ms"} for n in
                 ("hit_p50_ms", "hit_p95_ms", "miss_p50_ms", "miss_p90_ms")],
                recs, t0, t_end, t_end + c["mix"]["drain_s"], 0.0)
            late = [(r.sent - r.due) * 1e3 for r in recs]
            print(json.dumps({
                "rate_per_s": rate, "sent": len(recs),
                "misses_sent": len(misses),
                "misses_done_by_close": len(misses) - open_at_close,
                "backlog_at_close": open_at_close,
                "failed": sum(1 for r in recs if r.error or not r.done),
                "late_p99_ms": run.percentile(late, 99),
                **{k: v["value"] for k, v in out.items()}}), flush=True)
    sut.si.close()


if __name__ == "__main__":
    main()
