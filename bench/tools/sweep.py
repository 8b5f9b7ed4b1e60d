#!/usr/bin/env python3
"""Find the knee of a streamed multicast cell: the highest offered rate
whose delivered rate keeps pace over the window with no growing backlog,
and whose delivery tail does not grow with the window.

    python3 bench/tools/sweep.py --traffic steady --seconds 10 30 \
        --seeds 1 2 --rates 8000 16000 32000

Runs, in this one process (a chip belongs to one process), the
saturated cell of the same configuration once to read its delivered
rate, then the open-loop mix ``--traffic`` (a file of
``bench/traffic/``, listed as a cell or not) at each fraction of that
rate, and prints one line per point: offered and delivered msgs/s,
messages still undelivered at the window's end, delivery rounds and
latency percentiles, and host ms per round, for each window length in
``--seconds`` and seed in ``--seeds``.  ``--rates`` gives the offered
rates in msgs/s instead.  The knee goes into the mix's file by hand,
as a number in msgs/s.
"""

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic", default="steady")
    ap.add_argument("--config", default="spindle_testbed")
    ap.add_argument("--saturated", default="testbed.saturated")
    ap.add_argument("--seconds", type=float, nargs="+", default=[3.0])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1])
    ap.add_argument("--fractions", type=float, nargs="+",
                    default=[0.5, 0.7, 0.8, 0.9, 0.95, 1.0, 1.1])
    ap.add_argument("--rates", type=float, nargs="+",
                    help="offered rates in msgs/s, in place of fractions")
    args = ap.parse_args()
    entry = {"name": f"sweep.{args.traffic}", "config": args.config,
             "traffic": args.traffic, "chips": 1}
    run, _ = bench_run.run_cell(args.saturated, args.seeds[0],
                                args.seconds[0], False)
    sat = run.values["delivered_in_window"] / run.window_s
    print(json.dumps({"saturated_msgs_per_s": sat,
                      "round_ms": run.window_s
                      / run.values["window_rounds"] * 1e3}), flush=True)
    points = [(rate, secs, seed)
              for rate in args.rates or [f * sat for f in args.fractions]
              for secs in args.seconds for seed in args.seeds]
    for rate, secs, seed in points:
        run, line = bench_run.run_cell(
            entry["name"], seed, secs, False, entry=entry,
            mix_override={"rate_msgs_per_s": rate})
        v = run.values
        lat, rnd = v["latency_ms"], v["delivery_rounds"]
        print(json.dumps({
            "fraction": rate / sat, "offered_msgs_per_s": rate,
            "seconds": secs, "seed": seed,
            "arrived_msgs_per_s": run.attempted / run.window_s,
            "delivered_msgs_per_s": v["delivered_in_window"]
            / run.window_s,
            "undelivered_at_window_end": run.attempted
            - v["delivered_in_window"],
            "nulls_per_round": v["nulls_in_window"] / v["window_rounds"],
            "delivery_rounds_p50_p95": np.percentile(rnd,
                                                     [50, 95]).tolist(),
            "latency_ms_p50_p95_p99": np.percentile(lat,
                                                    [50, 95, 99]).tolist(),
            "round_ms": run.window_s / v["window_rounds"] * 1e3,
            "long_rounds": v["long_rounds"],
            "lateness_ms_p95": float(np.percentile(v["lateness_s"], 95) * 1e3),
            "correct": line["correct"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
