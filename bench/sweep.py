"""Find a cell's knee once: the same set-up, then one window per rate.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,4,6

Each rate gets a fresh plane and fresh Packrat pools over the one engine,
and a steady mix with the cell's lengths and lead-in at that rate.  Prints one JSON
row per rate (offered and completed tokens per second, TTFT and TPOT
percentiles, failures, how long the drain took); the knee is the highest
rate whose completed tokens keep up with the offered ones without a
growing queue.  Used when a cell is defined, not by the benchmark's runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import run  # noqa: F401  (puts the repo and src/ on sys.path)
from bench import traffic
from bench.client import build_stack, drive, profile_plane
from bench.e2e import summarize


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    run.keep_logs_inside()
    cell = run.load_cell(args.workload)
    run.device_check(cell.chips)
    run.configure_cache()
    from repro.serving import RealPlane
    su = run.set_up(cell, args.seed)
    profiles = None
    for rate in (float(r) for r in args.rates.split(",")):
        plane = RealPlane(su.spans, su.settings.units, max_runners=256)
        if profiles is None:
            profiles = profile_plane(plane, su.settings)
        stack = build_stack(plane, profiles, su.settings,
                            cell.mix.output_tokens)
        mix = dataclasses.replace(cell.mix, phases=((None, rate),))
        arrivals = traffic.arrivals(mix, args.seconds, args.seed)
        end = drive(stack, arrivals, decode_steps=mix.output_tokens,
                    prompt_bucket=su.engine.default_seq_bucket,
                    window_s=args.seconds, drain_s=run.DRAIN_S,
                    lead_s=mix.lead_in_s)
        plane.close()
        row = summarize(stack.window_records(),
                        decode_steps=mix.output_tokens,
                        window_s=args.seconds, drain_end=end,
                        tokens_in_window=stack.tokens_in_window)
        row.update(rate_rps=rate,
                   drain_s=end - mix.lead_in_s - args.seconds,
                   offered_tok_s=rate * (mix.output_tokens + 1),
                   unit_split=stack.unit_split,
                   plans={p: str(s.reconfig_log[-1][2])
                          for p, s in stack.servers.items()},
                   reconfigs={p: len(s.reconfig_log)
                              for p, s in stack.servers.items()})
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
