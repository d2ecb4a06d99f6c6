#!/usr/bin/env python3
"""Read the numbers ``correct`` compares for the program and for its
control, on several seeds, in one process.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...]

Each seed is one run of the cell as ``bench/run.py`` makes it (its own
weights, traffic and window at the cell's load), judged with the float8
control in the program's place: the reference at the precision below the
configuration's bfloat16, whose first-choice token is compared at every
served position instead of the served one. One JSON line per seed gives
the control's verdict (``correct``, which has to be false), the program's
verdict on the same run (``program_correct``) and both readings of the
logit gap. A limit belongs above every program reading and below every
control reading (PERF.md). The benchmark's own runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    import run
    for seed in args.seeds:
        res = run.run(["--workload", args.workload, "--seed", str(seed),
                       "--seconds", str(args.seconds)], control=True)
        checks = res["checks"]
        prog = res["program_logit_gap"]
        limit = checks["logit_gap"]["limit"]
        program_correct = (res["failed"] == 0 and prog is not None
                           and prog <= limit
                           and all(v["value"] <= v["limit"]
                                   for k, v in checks.items()
                                   if k != "logit_gap"))
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "program_correct": program_correct, "failed": res["failed"],
            "program_logit_gap": prog,
            "control_logit_gap": checks["logit_gap"]["value"],
            "limit": limit, "checks": checks}), flush=True)


if __name__ == "__main__":
    main()
