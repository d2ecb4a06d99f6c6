"""Benchmark driver: one module per paper table/figure. CSV on stdout;
JSON artifacts in experiments/bench/.

  PYTHONPATH=src python -m benchmarks.run            # all
  PYTHONPATH=src python -m benchmarks.run fig3 table1
  REPRO_BENCH_SCALE=4 ... (bigger stores; paper scale ~19)
"""
from __future__ import annotations

import sys
import time

from benchmarks import (bench_batched_serve, fig3_latency, fig4_scaling,
                        gen_cost, table1_hitrate, table2_threshold)

BENCHES = {
    "fig3": fig3_latency.main,
    "table1": table1_hitrate.main,
    "table2": table2_threshold.main,
    "fig4": fig4_scaling.main,
    "gen_cost": gen_cost.main,
    "batched_serve": lambda: bench_batched_serve.main([]),
}


def main(argv=None):
    names = (argv or sys.argv[1:]) or list(BENCHES)
    for n in names:
        t0 = time.time()
        print(f"# === {n} ===")
        BENCHES[n]()
        print(f"# {n} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
