"""Open-loop load benchmark — the offered-load -> (p99, goodput) curve of
the serve-plane substrate under the workload plane (DESIGN.md Sec. 10).

Unlike benchmarks/hotpath.py (wall clocks of the compiled programs) this
commits the PROTOCOL-TIME shape of the system under open-loop traffic:

* ``curve``  — a sweep of offered-load points (per-sender Poisson rate x
  scale), each a warmup+measure profile run through
  :func:`repro.load.run_profile` against a fresh group with a
  ``WindowSlack`` admission policy.  Per point: offered vs goodput
  (msgs/round), p50/p99/p999 latency in rounds and simulated us, shed
  count, queue/backlog highwater.  The sweep deliberately crosses
  saturation (~window/3 msgs per sender-round): past it, goodput must
  plateau while offered keeps climbing, shed must go positive, and p99
  must stay BOUNDED — that separation is the honesty constraint; a
  closed-loop harness could never show it.
* ``ramp``   — one staged_ramp profile (warmup -> steps -> overload) run
  end-to-end, the per-stage stats as a single LoadReport.
* ``one_program`` / warm trace deltas — the whole sweep rides ONE
  compiled one-round program per group shape: the cold run appends <=1
  TRACE_EVENTS entry, a second identical run appends 0.
* ``fused_serve`` — the serve-plane open-loop sweep run BOTH ways at
  each offered-load point: the per-round host loop vs the fused
  device-resident program (``run_profile(..., fused=True)``).  The two
  LoadReports must be byte-identical JSON (the fused path is an
  execution strategy, not a different system), the fused run must
  actually fuse with ``host_hops == 0``, and its wall-clock goodput
  (delivered requests per second, same runner, best-of-2) must be
  >= 2x the per-round loop's — the Spindle fused-dispatch claim at the
  committed loadtest shape, held by the smoke gate.

All latency/goodput numbers are deterministic (seeded arrivals, simulated
time), so the committed baseline regresses exactly; only ``*_wall_s`` is
machine-dependent.  Writes ``BENCH_loadtest.json`` at the repo root
(committed).  ``--smoke`` runs a 3-point sweep plus one fused-serve
point and FAILS (exit 1) on regression vs the committed baseline's
``smoke`` section: p99 blowup, goodput collapse, a vanished shed
signal, unbounded queues, extra compiles, a fused-serve run that fell
back / took host hops / diverged from the per-round loop, or a fused
speedup under the 2x floor; this is the CI ``loadtest-smoke`` gate.

Run:  PYTHONPATH=src python benchmarks/loadtest.py [--smoke] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

from repro import use_compilation_cache
from repro.api import Group, single_group, trace_snapshot
from repro.load import (Poisson, Profile, Stage, WindowSlack, run_profile,
                        staged_ramp)

ROOT = Path(__file__).resolve().parent.parent
BENCH_PATH = ROOT / "BENCH_loadtest.json"

# offered per sender-round = rate * scale; saturation ~ window/3 = 1.33,
# so both shapes end well past it (FULL: 1.6, 3.2; SMOKE: 3.0).
FULL = dict(n=5, senders=3, window=4, rate=0.4, warmup=30, measure=60,
            scales=(0.5, 1.0, 2.0, 4.0, 8.0),
            inflight_limit=8, queue_cap=32,
            ramp=dict(warmup=40, steps=(1.0, 2.0), rounds_per_stage=60,
                      overload=8.0))
SMOKE = dict(n=4, senders=2, window=4, rate=0.5, warmup=8, measure=16,
             scales=(1.0, 2.5, 6.0),
             inflight_limit=8, queue_cap=16,
             ramp=dict(warmup=10, steps=(1.0,), rounds_per_stage=16,
                       overload=6.0))

# serve-plane open-loop shapes: arrival lanes are KV slots, admission is
# ServeAdmission (queue_cap tail-drop per replica).  FULL crosses serve
# saturation (slots per replica bound concurrent decode); SMOKE is one
# past-saturation point, enough rounds that the per-round loop's
# dispatch overhead dominates — that is what the fused 2x gate measures.
SERVE_FULL = dict(replicas=2, slots=2, prompt=3, new_tokens=4, rate=0.5,
                  warmup=6, measure=24, scales=(0.5, 1.5, 3.0),
                  queue_cap=6)
SERVE_SMOKE = dict(replicas=2, slots=2, prompt=3, new_tokens=4, rate=0.5,
                   warmup=4, measure=12, scales=(1.5,), queue_cap=4)
FUSED_SPEEDUP_FLOOR = 2.0

# --smoke gates vs the committed baseline.  The protocol-time metrics are
# seeded-deterministic, so these factors only have to absorb legitimate
# protocol/policy tuning, not machine jitter; wall clock gets the usual
# 3x + slack treatment.
P99_FACTOR, P99_SLACK_ROUNDS = 1.5, 2.0
GOODPUT_FACTOR = 0.7
WALL_FACTOR, WALL_SLACK_S = 3.0, 0.1


def _group(shape):
    return Group(single_group(shape["n"], n_senders=shape["senders"],
                              msg_size=4096, window=shape["window"],
                              n_messages=0))


def _policy(shape):
    return WindowSlack(inflight_limit=shape["inflight_limit"],
                       queue_cap=shape["queue_cap"])


def _point(shape, scale, backend="graph"):
    """One offered-load point: warmup + measure stages at `scale`."""
    prof = Profile(arrivals=Poisson(rate=shape["rate"]), seed=7, stages=(
        Stage("warmup", shape["warmup"], scale),
        Stage("measure", shape["measure"], scale)))
    rep = run_profile(_group(shape), prof, _policy(shape),
                      backend=backend)
    st = rep.stage("measure")
    return {
        "scale": scale,
        "offered_per_round": st.offered_per_round,
        "goodput_per_round": st.goodput_per_round,
        "p50_rounds": st.p50_rounds,
        "p99_rounds": st.p99_rounds,
        "p999_rounds": st.p999_rounds,
        "p99_us": st.p99_us,
        "shed": st.shed,
        "max_queue_depth": st.max_queue_depth,
        "max_stream_backlog": st.max_stream_backlog,
    }


def bench_curve(shape, backend="graph"):
    """The offered-load sweep + the one-program trace accounting."""
    n0 = len(trace_snapshot())
    t0 = time.perf_counter()
    points = [_point(shape, s, backend) for s in shape["scales"]]
    cold_wall = time.perf_counter() - t0
    traces_cold = len(trace_snapshot()) - n0
    # second identical sweep: every stage of every point rides the cached
    # program — zero new traces, and the warm wall clock is the real cost
    n0 = len(trace_snapshot())
    t0 = time.perf_counter()
    for s in shape["scales"]:
        _point(shape, s, backend)
    warm_wall = time.perf_counter() - t0
    traces_warm = len(trace_snapshot()) - n0
    sat = [p for p in points
           if p["offered_per_round"] > p["goodput_per_round"] + 1e-9]
    return {
        "points": points,
        "saturated_points": len(sat),
        "overload_shed": int(points[-1]["shed"]),
        "traces_cold": int(traces_cold),
        "traces_warm": int(traces_warm),
        "one_program": bool(traces_cold <= 1 and traces_warm == 0),
        "cold_wall_s": round(cold_wall, 4),
        "warm_wall_s": round(warm_wall, 4),
    }


def bench_ramp(shape, backend="graph"):
    """One staged ramp (warmup -> steps -> overload) as a LoadReport."""
    r = shape["ramp"]
    prof = staged_ramp(Poisson(rate=shape["rate"]), warmup=r["warmup"],
                       steps=tuple(r["steps"]),
                       rounds_per_stage=r["rounds_per_stage"],
                       overload=r["overload"], seed=7)
    t0 = time.perf_counter()
    rep = run_profile(_group(shape), prof, _policy(shape),
                      backend=backend)
    wall = time.perf_counter() - t0
    out = rep.to_json()
    out["wall_s"] = round(wall, 4)
    return out


_SERVE_ARCH = "loadtest-serve"


def _serve_engines(shape):
    """shape["replicas"] fresh engines of a tiny real dense model; built
    once per suite so the jitted decode stays warm across points."""
    import jax
    from repro.models import layers, registry
    from repro.models.config import ModelConfig
    from repro.models.runtime import Runtime
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg = ModelConfig(name=_SERVE_ARCH, family="dense", n_layers=1,
                      d_model=32, n_heads=2, n_kv_heads=2, d_ff=64,
                      vocab_size=64, head_dim=16, tie_embeddings=True)
    registry.register(_SERVE_ARCH, lambda: cfg)   # idempotent overwrite
    params = layers.init_tree(registry.param_specs(cfg), jax.random.key(0))
    return [ServeEngine(_SERVE_ARCH, params, cfg,
                        EngineConfig(max_batch=shape["slots"], max_len=32),
                        Runtime())
            for _ in range(shape["replicas"])]


def bench_fused_serve(shape):
    """The serve-plane sweep, each point run through the per-round loop
    AND the fused device program: byte-identical LoadReport JSON, zero
    host hops fused, and the wall-clock goodput ratio (best-of-2 per
    path, same runner) — the fused-dispatch speedup the CI gate holds
    at >= FUSED_SPEEDUP_FLOOR."""
    from repro.load import ServeAdmission
    from repro.serve.fanout import ReplicatedEngine

    engines = _serve_engines(shape)

    def run_once(scale, fused):
        prof = Profile(arrivals=Poisson(rate=shape["rate"]), seed=7,
                       stages=(Stage("warmup", shape["warmup"], scale),
                               Stage("measure", shape["measure"], scale)))
        rep_eng = ReplicatedEngine(engines, subscribers_per_replica=2,
                                   window=4, backend="graph")
        rep_eng.reset()
        t0 = time.perf_counter()
        rep = run_profile(rep_eng, prof,
                          ServeAdmission(queue_cap=shape["queue_cap"]),
                          max_new_tokens=shape["new_tokens"],
                          prompt_len=shape["prompt"], fused=fused)
        return time.perf_counter() - t0, rep

    points = []
    for scale in shape["scales"]:
        walls, reps = {}, {}
        for fused in (False, True):
            walls[fused] = float("inf")
            for _ in range(2):
                w, rep = run_once(scale, fused)
                walls[fused] = min(walls[fused], w)
                reps[fused] = rep
        serve = reps[True].run_report.extras["serve"]
        st = reps[True].stage("measure")
        delivered = reps[True].totals["delivered"]
        points.append({
            "scale": scale,
            "offered_per_round": st.offered_per_round,
            "goodput_per_round": st.goodput_per_round,
            "p99_rounds": st.p99_rounds,
            "shed": int(reps[True].totals["shed"]),
            "fused": bool(serve["fused"]),
            "fused_fallback": serve.get("fused_fallback"),
            "host_hops": int(serve["host_hops"]),
            "json_identical": bool(
                reps[True].json_str() == reps[False].json_str()),
            "wall_unfused_s": round(walls[False], 4),
            "wall_fused_s": round(walls[True], 4),
            "goodput_unfused_per_s": round(delivered / walls[False], 1),
            "goodput_fused_per_s": round(delivered / walls[True], 1),
            "speedup": round(walls[False] / walls[True], 2),
        })
    return {
        "points": points,
        "min_speedup": min(p["speedup"] for p in points),
        "all_fused": all(p["fused"] for p in points),
        "all_zero_host_hops": all(p["host_hops"] == 0 for p in points),
        "all_json_identical": all(p["json_identical"] for p in points),
    }


def run_suite(shape, serve_shape):
    return {"curve": bench_curve(shape), "ramp": bench_ramp(shape),
            "fused_serve": bench_fused_serve(serve_shape)}


def _gate_curve(cur, base, shape):
    """Regression checks for one curve vs its committed baseline."""
    failures = []
    for p, ref in zip(cur["points"], base.get("points", [])):
        tag = f"scale={p['scale']:g}"
        limit = P99_FACTOR * ref["p99_rounds"] + P99_SLACK_ROUNDS
        ok = p["p99_rounds"] <= limit
        print(f"{tag}: p99={p['p99_rounds']:.0f} rounds "
              f"(baseline {ref['p99_rounds']:.0f}, limit {limit:.0f}) "
              f"{'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(f"{tag}.p99_rounds")
        floor = GOODPUT_FACTOR * ref["goodput_per_round"]
        ok = p["goodput_per_round"] >= floor
        print(f"{tag}: goodput={p['goodput_per_round']:.2f}/round "
              f"(baseline {ref['goodput_per_round']:.2f}, floor "
              f"{floor:.2f}) {'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(f"{tag}.goodput")
    lanes = shape["senders"]
    depth_cap = shape["queue_cap"] * lanes
    if cur["points"][-1]["max_queue_depth"] > depth_cap:
        print(f"overload queue depth {cur['points'][-1]['max_queue_depth']}"
              f" exceeds cap x lanes = {depth_cap}")
        failures.append("overload.max_queue_depth")
    if cur["overload_shed"] <= 0:
        print("overload point shed nothing — the sweep no longer crosses "
              "saturation (or admission stopped shedding)")
        failures.append("overload.shed")
    if cur["saturated_points"] < 1:
        print("no saturated point in the sweep")
        failures.append("curve.saturated_points")
    if not cur["one_program"]:
        print(f"trace accounting: cold={cur['traces_cold']} "
              f"warm={cur['traces_warm']} (want <=1 / 0)")
        failures.append("curve.one_program")
    ref_wall = base.get("warm_wall_s")
    if ref_wall is not None:
        limit = WALL_FACTOR * ref_wall + WALL_SLACK_S
        ok = cur["warm_wall_s"] <= limit
        print(f"warm sweep wall: {cur['warm_wall_s']:.3f}s (baseline "
              f"{ref_wall:.3f}s, limit {limit:.3f}s) "
              f"{'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append("curve.warm_wall_s")
    return failures


def _gate_fused_serve(fs):
    """The fused-serve contract: every point fuses, takes zero host
    hops, matches the per-round loop byte-for-byte, and the speedup
    ratio holds the floor.  The ratio compares two runs on the SAME
    runner, so unlike absolute wall clocks it cannot flake on a slow
    machine — no baseline needed."""
    failures = []
    for p in fs["points"]:
        tag = f"fused_serve scale={p['scale']:g}"
        if not p["fused"]:
            print(f"{tag}: fell back to the per-round loop "
                  f"({p['fused_fallback']})")
            failures.append(f"{tag}.fused")
        if p["host_hops"] != 0:
            print(f"{tag}: {p['host_hops']} host hops in a fused run "
                  "(want 0)")
            failures.append(f"{tag}.host_hops")
        if not p["json_identical"]:
            print(f"{tag}: fused LoadReport JSON differs from the "
                  "per-round loop's")
            failures.append(f"{tag}.json_identical")
        ok = p["speedup"] >= FUSED_SPEEDUP_FLOOR
        print(f"{tag}: goodput {p['goodput_fused_per_s']}/s fused vs "
              f"{p['goodput_unfused_per_s']}/s per-round loop "
              f"({p['speedup']}x, floor {FUSED_SPEEDUP_FLOOR}x) "
              f"{'OK' if ok else 'REGRESSION'}")
        if not ok:
            failures.append(f"{tag}.speedup")
    return failures


def smoke_gate(baseline_path: Path) -> int:
    results = run_suite(SMOKE, SERVE_SMOKE)
    failures = _gate_fused_serve(results["fused_serve"])
    if not baseline_path.exists():
        print(f"no baseline at {baseline_path}; curve measured only")
        print(json.dumps(results, indent=1))
        return 1 if failures else 0
    base = json.loads(baseline_path.read_text()).get("smoke", {})
    failures += _gate_curve(results["curve"], base.get("curve", {}), SMOKE)
    if failures:
        print(f"loadtest-smoke FAILED: {failures}")
        return 1
    print("loadtest-smoke passed")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="3-point sweep; fail on regression vs baseline")
    ap.add_argument("--json", type=Path, default=BENCH_PATH)
    args = ap.parse_args()
    use_compilation_cache()
    if args.smoke:
        return smoke_gate(args.json)
    record = {
        "full": run_suite(FULL, SERVE_FULL),
        "smoke": run_suite(SMOKE, SERVE_SMOKE),
        "scenario": {
            "full": {k: (list(v) if isinstance(v, tuple) else v)
                     for k, v in FULL.items()},
            "smoke": {k: (list(v) if isinstance(v, tuple) else v)
                      for k, v in SMOKE.items()},
            "serve_full": {k: (list(v) if isinstance(v, tuple) else v)
                           for k, v in SERVE_FULL.items()},
            "serve_smoke": {k: (list(v) if isinstance(v, tuple) else v)
                            for k, v in SERVE_SMOKE.items()},
        },
    }
    args.json.write_text(json.dumps(record, indent=1) + "\n")
    print(json.dumps(record, indent=1))
    print(f"-> {args.json}")
    full_curve = record["full"]["curve"]
    pts = full_curve["points"]
    goodputs = [p["goodput_per_round"] for p in pts]
    # acceptance: the curve rises to saturation then PLATEAUS (goodput at
    # max offered within 25% of the best point) while p99 stays bounded
    # and shed goes positive — the honest-overload shape.
    fs = record["full"]["fused_serve"]
    ok = (full_curve["saturated_points"] >= 1
          and full_curve["overload_shed"] > 0
          and pts[-1]["offered_per_round"] > pts[-1]["goodput_per_round"]
          and goodputs[-1] >= 0.75 * max(goodputs)
          and pts[-1]["p99_rounds"] <= 3 * (FULL["queue_cap"]
                                            + FULL["inflight_limit"]) + 10
          and pts[-1]["max_queue_depth"]
              <= FULL["queue_cap"] * FULL["senders"]
          and full_curve["one_program"]
          and record["smoke"]["curve"]["one_program"]
          and fs["all_fused"] and fs["all_zero_host_hops"]
          and fs["all_json_identical"]
          and fs["min_speedup"] >= FUSED_SPEEDUP_FLOOR
          and record["smoke"]["fused_serve"]["min_speedup"]
              >= FUSED_SPEEDUP_FLOOR)
    print("acceptance:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
