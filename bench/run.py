#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is a ``workloads`` entry of ``BENCHMARK.json``; its
configuration names the runner that runs it (``bench/runners/``), its
traffic mix is a data file (``bench/traffic/``), and each of its metrics
is read by a file of its own (``bench/metrics/``).  A run needs as many
accelerator chips as the cell asks for and exits non-zero, printing no
result, without them.  It warms up (counted as ``setup_s``), measures
for ``--seconds``, checks what the timed path produced against a plain
reference, and prints one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the end-to-end metrics, or with ``--trace 1``
the per-layer ones from a profiler trace of the window), ``device``,
``breakdown`` when traced, and last ``checks``, each number compared
beside its limit.  The same numbers end standard error.
"""

import time

CLOCK0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchlib import harness  # noqa: E402


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             require_chip: bool = True, config_override=None,
             mix_override=None, wrap_program=None, trace_names=None,
             entry=None):
    """One run of ``workload``: the :class:`harness.Run` and the result
    line.  The keyword arguments serve the tools and the self-check,
    which rehearse a cell on the CPU at a small size, break its timed
    path, or run a mix that ``BENCHMARK.json`` does not list yet
    (``entry``, see :func:`harness.find_cell`)."""
    cell = harness.find_cell(workload, entry)
    n_chips = int(cell.entry["chips"])
    import jax

    if require_chip:
        devices = harness.require_devices(n_chips)
    else:
        devices = jax.devices()[:n_chips]
    harness.import_program()
    counter = harness.CompileCounter()
    config = dict(cell.config, **(config_override or {}))
    ctx = types.SimpleNamespace(
        workload=workload, config=config,
        mix=dict(cell.mix, **(mix_override or {})), seed=int(seed),
        seconds=float(seconds), traced=bool(traced),
        clock0=CLOCK0, counter=counter,
        devices=devices, wrap_program=wrap_program,
        trace_names=trace_names or {})
    run = harness.runner(config["runner"]).drive(ctx)
    line = harness.result_line(
        cell, run, harness.device_info(devices, run.memory_peak_bytes),
        traced)
    return run, line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        run, line = run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace))
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    v = run.values
    harness.log(f"setup {run.setup_s:.3f} s ({v['setup_compiles']} "
                f"programs compiled or loaded, {v['setup_cache_hits']} "
                "from the persistent cache); window "
                f"{run.window_s:.3f} s, {v['window_rounds']} rounds, "
                f"drain {v['drain_rounds']} rounds, "
                f"{v['long_rounds']} rounds over 50 ms; reference "
                f"{v['reference_s']:.3f} s")
    late = v.get("lateness_s")
    if late is not None and len(late):
        harness.log(f"generator lateness: mean {late.mean() * 1e3:.4f} "
                    f"ms, p95 {np.percentile(late, 95) * 1e3:.4f} ms, "
                    f"max {late.max() * 1e3:.4f} ms over {len(late)} "
                    "arrivals")
    print(f"correct: {line['correct']}", file=sys.stderr)
    for name, c in line["checks"].items():
        print(f"check {name}: {c['value']:g} (limit {c['limit']:g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
