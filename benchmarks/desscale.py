"""Two-phase DES scale-out benchmark (DESIGN.md Sec. 12).

Times the same fleet-scale scenario three ways at N in {64, 256, 1024}:

* ``legacy_s``  — the single-phase ``des-loop`` event loop
  (:class:`repro.core.simulator.Simulator`), the pre-split baseline.
* ``phase1_s``  — :func:`repro.core.desgraph.simulate`, the slimmed
  event-level pass that assigns timestamps and emits the compact
  event/delivery graph (merged per-(subgroup, source) wire streams).
* ``phase2_s``  — :func:`repro.core.desreplay.replay`, the vectorized
  numpy reconstruction of delivery logs, costs and the
  :class:`~repro.core.simulator.SimResult` from that graph.

``two_phase_s`` = phase1 + phase2 is what ``backend="des"`` costs;
``speedup`` = legacy / two_phase.  Every point also asserts the
two-phase :class:`SimResult` and per-member delivery sequences are
BIT-IDENTICAL to the legacy loop's — the differential contract the
split lives under.  Legacy and two-phase timings are interleaved within
each repeat (best-of) so box noise can't skew the ratio.

Writes ``BENCH_desscale.json`` at the repo root (committed).  ``--smoke``
runs only the CI gate — bit-identity vs ``des-loop`` at N=64 and
speedup >= 5x at N=256 — and FAILS (exit 1) on either; this is the CI
``des-scale`` job.

Run:  PYTHONPATH=src python benchmarks/desscale.py [--smoke] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

from repro import use_compilation_cache
from repro.core import desgraph, desreplay
from repro.core import simulator as sim
from repro.core.group import DESLoopBackend, Group, single_group

ROOT = Path(__file__).resolve().parent.parent
BENCH_PATH = ROOT / "BENCH_desscale.json"

# Steady-state fleet points: enough in-flight traffic per sender that the
# wire dominates (the regime the vectorized replay targets).  The 1024
# point backs off n_messages so the legacy loop stays tractable; the
# 4096-node point lives in tests/test_des_scale.py under ``-m soak``
# (conformance, not wall clock).
SCALES = (
    dict(n=64, senders=8, msgs=32, window=32),
    dict(n=256, senders=8, msgs=32, window=32),
    dict(n=1024, senders=8, msgs=4, window=16),
)
SPEEDUP_FLOOR = 5.0                  # gated at N=256, the mid-scale point
GATE_N = 256
IDENTITY_N = 64                      # the smoke bit-identity point


def _cfg(n, senders, msgs, window):
    return single_group(n, n_senders=senders, msg_size=4096,
                        window=window, n_messages=msgs)


def _sim_cfg(cfg):
    g = Group(cfg)
    counts = {i: g.send_counts(i, cfg)
              for i in range(len(cfg.subgroups))}
    return DESLoopBackend._lower(cfg, counts)


def _eq(a, b):
    """Bit-exact structural equality over results (NaN == NaN)."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        a, b = np.asarray(a), np.asarray(b)
        return (a.shape == b.shape and a.dtype == b.dtype
                and bool(np.array_equal(a, b, equal_nan=(
                    a.dtype.kind == "f"))))
    if isinstance(a, dict):
        return (isinstance(b, dict) and set(a) == set(b)
                and all(_eq(a[k], b[k]) for k in a))
    if isinstance(a, (list, tuple)):
        return (isinstance(b, (list, tuple)) and len(a) == len(b)
                and all(_eq(x, y) for x, y in zip(a, b)))
    if isinstance(a, float) and isinstance(b, float):
        return (a != a and b != b) or a == b
    return a == b


def _log_digest(logs):
    return {gid: {int(n): log.sequence(n)
                  for n in log.delivered_seq}
            for gid, log in logs.items()}


def bench_point(shape, repeats=3):
    """One scale point: interleaved best-of timings plus bit-identity."""
    scfg = _sim_cfg(_cfg(**shape))
    legacy = p1 = p2 = float("inf")
    res_legacy = res_two = legacy_logs = two_logs = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        simulator = sim.Simulator(scfg)
        res_legacy = simulator.run()
        legacy = min(legacy, time.perf_counter() - t0)

        t0 = time.perf_counter()
        graph = desgraph.simulate(scfg)
        p1 = min(p1, time.perf_counter() - t0)
        t0 = time.perf_counter()
        res_two = desreplay.replay(graph)
        p2 = min(p2, time.perf_counter() - t0)
    from repro.core.group import _des_logs
    legacy_logs = _des_logs(simulator.groups)
    two_logs = _des_logs(graph.groups)
    identical = (_eq(vars(res_legacy), vars(res_two))
                 and _eq(_log_digest(legacy_logs), _log_digest(two_logs)))
    two_phase = p1 + p2
    return {
        "n_nodes": shape["n"],
        "senders": shape["senders"],
        "n_messages": shape["msgs"],
        "window": shape["window"],
        "legacy_s": round(legacy, 4),
        "phase1_s": round(p1, 4),
        "phase2_s": round(p2, 4),
        "two_phase_s": round(two_phase, 4),
        "speedup": round(legacy / two_phase, 2),
        "bit_identical": bool(identical),
        "delivered_app_msgs": int(res_two.delivered_app_msgs),
        "stalled": bool(res_two.stalled),
    }


def smoke_gate() -> int:
    """The CI ``des-scale`` gate: N=64 bit-identity + N=256 >= 5x."""
    failures = []
    small = bench_point(next(s for s in SCALES if s["n"] == IDENTITY_N),
                        repeats=2)
    print(f"N={IDENTITY_N}: bit_identical={small['bit_identical']} "
          f"(legacy {small['legacy_s']}s, two-phase "
          f"{small['two_phase_s']}s)")
    if not small["bit_identical"]:
        failures.append(f"n{IDENTITY_N}.bit_identical")
    if small["stalled"]:
        failures.append(f"n{IDENTITY_N}.stalled")
    mid = bench_point(next(s for s in SCALES if s["n"] == GATE_N),
                      repeats=2)
    status = "OK" if mid["speedup"] >= SPEEDUP_FLOOR else "REGRESSION"
    print(f"N={GATE_N}: speedup {mid['speedup']}x (floor "
          f"{SPEEDUP_FLOOR}x; legacy {mid['legacy_s']}s, phase1 "
          f"{mid['phase1_s']}s, phase2 {mid['phase2_s']}s) {status}")
    if mid["speedup"] < SPEEDUP_FLOOR:
        failures.append(f"n{GATE_N}.speedup")
    if not mid["bit_identical"]:
        failures.append(f"n{GATE_N}.bit_identical")
    if failures:
        print(f"des-scale smoke FAILED: {failures}")
        return 1
    print("des-scale smoke passed")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: N=64 bit-identity + N=256 >= 5x")
    ap.add_argument("--json", type=Path, default=BENCH_PATH)
    args = ap.parse_args()
    use_compilation_cache()
    if args.smoke:
        return smoke_gate()
    points = [bench_point(s) for s in SCALES]
    record = {
        "speedup_floor_at_n256": SPEEDUP_FLOOR,
        "scales": points,
        "scenario": {"msg_size": 4096, "points": [dict(s) for s in SCALES]},
    }
    args.json.write_text(json.dumps(record, indent=1) + "\n")
    print(json.dumps(record, indent=1))
    print(f"-> {args.json}")
    gate = next(p for p in points if p["n_nodes"] == GATE_N)
    ok = (all(p["bit_identical"] and not p["stalled"] for p in points)
          and gate["speedup"] >= SPEEDUP_FLOOR)
    print("acceptance:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
