"""Readings for the check's limits: the program on many seeds, and the
fp8 control on some of them, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,... --seconds <s> --control 3

Each seed is a whole run of the cell (set-up, a short window at the
cell's own load, the check); the first ``--control`` seeds also compute
the control's numbers on the same rows.  Prints one JSON row per seed
and a last row with the largest program reading and the smallest control
reading of each number.  Used when limits are set, not by the
benchmark's runs.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    args = ap.parse_args(argv)
    run.keep_logs_inside()
    seeds = [int(s) for s in args.seeds.split(",")]
    program: dict = {}
    ctrl: dict = {}
    for i, seed in enumerate(seeds):
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           t_start=time.perf_counter(),
                           control=i < args.control)
        row = {"seed": seed, "correct": out["correct"],
               "failed": out["failed"], "attempted": out["attempted"],
               "check": out["check"], "control": out.get("control"),
               "setup_s": out["metrics"]["setup_s"]["value"],
               "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        print(json.dumps(row), flush=True)
        for k in ("gap", "err"):
            program.setdefault(k, []).append(out["check"][k]["value"])
            if "control" in out:
                ctrl.setdefault(k, []).append(out["control"][k])
    print(json.dumps({"program_max": {k: max(v) for k, v in program.items()},
                      "control_min": {k: min(v) for k, v in ctrl.items()},
                      "seeds": len(seeds)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
