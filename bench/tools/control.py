#!/usr/bin/env python3
"""Run a streamed multicast cell with its control, or with one of the
faults of :mod:`benchlib.faults`, in place of the sound timed path, on
several seeds in this one process, and print each run's numbers
compared, or the error where the broken run crashed.  Every such run
has to read ``correct`` false.

    python3 bench/tools/control.py --workload testbed.saturated \
        --seconds 10 --seeds 101 102 103 [--fault control]

The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import run as bench_run  # noqa: E402
from benchlib import faults  # noqa: E402

FAULTS = {
    "control": lambda: faults.lose_one_message(after_round=100),
    "state_unchanged": faults.state_unchanged,
    "half_batch": faults.half_batch,
    "altered_answer": lambda: faults.altered_answer(at_round=100),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", choices=sorted(FAULTS), default="control")
    args = ap.parse_args()
    for seed in args.seeds:
        out = {"workload": args.workload, "fault": args.fault, "seed": seed}
        try:
            _, line = bench_run.run_cell(args.workload, seed, args.seconds,
                                         False,
                                         wrap_program=FAULTS[args.fault]())
            out.update(correct=line["correct"], checks=line["checks"])
        except Exception as e:        # a run that crashes has failed too
            out.update(correct=False, crashed=f"{type(e).__name__}: {e}")
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
