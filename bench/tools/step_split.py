#!/usr/bin/env python3
"""Split the host round of a streamed multicast cell into the program's
own spans, on the device trace's clock, and print one JSON line.

    python3 bench/tools/step_split.py --workload testbed.saturated \
        --seed 1 --seconds 51 [--trace 0]

It runs the cell's window as ``bench/runners/multicast_stream.py`` does
(the same configuration, traffic mix, warm-up and ``bench.*`` spans)
and reads ``GroupStream.host_syncs`` over the window.  Traced (the
default), it reduces the profiler trace of the window with
:mod:`benchlib.spans`: per round, the time in ``spindle.stream.dispatch``
and ``spindle.stream.readback`` and the self time of
``spindle.stream.step`` (host bookkeeping); the device's idle time by
the innermost span over each part of it; the share of the round
program's executions that start inside a ``spindle.stream.step`` span
(the host and device clocks agree), and how far outside the others
start (least, median and most, in µs; negative where a start comes
before the next step span opens); and, for each round over 50 ms,
which part of the step held most of it.  It checks no output against
the reference: ``bench/run.py`` does that.  The benchmark's own runs
never run this.
"""

import argparse
import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import numpy as np  # noqa: E402

from benchlib import harness, spans, trace  # noqa: E402
from benchlib.traffic import Offer  # noqa: E402

STEP, DISPATCH, READBACK = ("spindle.stream.step", "spindle.stream.dispatch",
                            "spindle.stream.readback")
LONG_S = 0.05


def split(path: str, program: str, *, device_prefix: str = "/device:TPU:",
          op_line: str = "XLA Ops", module_line: str = "XLA Modules"):
    """The program's spans in the traced window of the ``.xplane.pb`` at
    ``path``; ``program`` is the round program's XLA module name."""
    ops, modules, _ = trace.read_events(
        path, device_prefix=device_prefix, op_line=op_line,
        module_line=module_line, span_prefix="bench.")
    found = spans.read_spans(path)
    lo, hi, _, thread = next(sp for sp in found if sp[2] == "bench.window")
    plane = next(iter(ops), None)
    busy = trace.union_ns(trace.clip(np.asarray(
        [(a, b) for a, b, _ in ops.get(plane, [])], float).reshape(-1, 2),
        lo, hi))
    mine = [sp for sp in found
            if sp[3] == thread and sp[2] != "bench.window"]
    pieces = spans.innermost(mine, lo, hi, "bench.window")
    gaps = spans.split_gaps(spans.idle_gaps(busy, lo, hi), pieces)
    tab = spans.table(found, lo, hi)
    steps = [sp for sp in mine if sp[2] == STEP and sp[0] >= lo
             and sp[1] <= hi]
    n = len(steps)
    if not n:
        raise RuntimeError(f"no {STEP} span in the traced window")
    execs = np.asarray([a for a, b, name in modules.get(plane, [])
                        if (name == program or name.startswith(program + "("))
                        and lo <= a <= hi], float)
    long_steps = {}
    for s0, s1, _, _ in steps:
        if (s1 - s0) * 1e-9 > LONG_S:
            part = spans.table(mine, s0, s1)
            parts = {DISPATCH: part.get(DISPATCH, (0, 0.0))[1],
                     READBACK: part.get(READBACK, (0, 0.0))[1],
                     "bookkeeping": part[STEP].self_s}
            top = max(parts, key=parts.get)
            long_steps[top] = long_steps.get(top, 0) + 1
    per_round = {key: tab[name].total_s / n * 1e3 if name in tab else 0.0
                 for key, name in (("step_dispatch_ms", DISPATCH),
                                   ("step_readback_ms", READBACK))}
    per_round["step_host_ms"] = tab[STEP].self_s / n * 1e3
    idle_s = sum(gaps.values())
    out = dict(
        per_round, step_spans=n, window_s=(hi - lo) * 1e-9,
        busy_s=float((busy[:, 1] - busy[:, 0]).sum()) * 1e-9,
        idle_gaps=sorted(([k, v, 100.0 * v / idle_s]
                          for k, v in gaps.items()), key=lambda g: -g[1]),
        spans={k: list(v) for k, v in sorted(tab.items())},
        program_executions=len(execs),
        long_steps=long_steps)
    if len(execs):
        off = spans.outside_ns(execs, steps)
        out["program_starts_in_step_pct"] = 100.0 * float((off == 0).mean())
        if (off != 0).any():
            out["program_starts_outside_step_us"] = (np.percentile(
                off[off != 0], [0, 50, 100]) * 1e-3).tolist()
    return out


def measure(workload: str, seed: int, seconds: float, traced: bool, *,
            require_chip: bool = True, config_override=None,
            trace_names=None):
    """One window of ``workload``; the numbers as a dict."""
    cell = harness.find_cell(workload)
    import jax

    devices = (harness.require_devices(int(cell.entry["chips"]))
               if require_chip else jax.devices())
    harness.import_program()
    from repro import api

    config = dict(cell.config, **(config_override or {}))
    cfg = harness.runner(config["runner"])._group_config(config)
    backend = config.get("backend", "graph")
    n_s = int(config["n_senders"])
    warm = api.Group(cfg).stream(backend=backend)
    for k in range(4):
        warm.step(np.full((1, n_s), k, np.int32))
    del warm
    offer = Offer(cell.mix, n_s, seed, float(seconds))
    stream = api.Group(cfg).stream(backend=backend)
    program = "jit_" + stream._program.__name__
    trace_dir = os.path.join(harness.OUT, "split-" + workload)
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=trace.options())
    syncs0 = stream.host_syncs
    ends = []
    backlog = np.zeros(n_s, np.int64)
    ann = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with ann("bench.window"):
        while True:
            now = time.perf_counter() - t0
            last = now >= seconds
            if last and offer.due is None:
                break
            with ann("bench.traffic"):
                ready = offer.take(now, backlog)
            with ann("bench.step"):
                view = stream.step(ready[None, :])
            ends.append(time.perf_counter() - t0)
            backlog = np.asarray(view.backlog[0, :n_s], np.int64)
            if last:
                break
    if traced:
        jax.profiler.stop_trace()
    rounds = len(ends)
    out = {"workload": workload, "seed": seed, "traced": traced,
           "device": devices[0].device_kind, "window_rounds": rounds,
           "round_ms": ends[-1] / rounds * 1e3,
           "readbacks_per_round": (stream.host_syncs - syncs0) / rounds,
           "long_rounds": int((np.diff(ends, prepend=0.0) > LONG_S).sum())}
    if traced:
        out.update(split(trace.find_xplane(trace_dir), program,
                         **(trace_names or {})))
        shutil.rmtree(trace_dir, ignore_errors=True)
        out["split_share_of_round_pct"] = 100.0 * (
            out["step_dispatch_ms"] + out["step_readback_ms"]
            + out["step_host_ms"]) / out["round_ms"]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", default="testbed.saturated")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    args = ap.parse_args()
    try:
        out = measure(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    except harness.NoDevice as e:
        print(f"step_split: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
