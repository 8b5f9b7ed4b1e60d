"""Hot-path wall-clock benchmark — the perf trajectory of the graph/pallas
substrate (compile-once scan, vectorized reconstruction, batched
multi-scenario execution).

Measures, with real wall clocks (unlike benchmarks/run.py, whose numbers
are simulated-time):

* ``repeated_run``  — the same ``Group.run`` twice per backend: the first
  call traces+compiles the scan program, the second hits the jit cache
  (:func:`repro.core.group._scan_program`), so cold/warm is the compile-
  once win and warm is the true per-round/per-message hot-path cost.
* ``window_grid``   — an 8-point Fig.6-style window sweep: 8 sequential
  ``Group.run`` calls vs ONE ``Group.run_batch`` program, asserting the
  per-point delivery logs are byte-identical.
* ``many_topics``   — the many-group dimension (a 16-topic DDS domain):
  ONE stacked compiled program for all topics vs 16 sequential
  single-topic runs, asserting per-topic delivery logs are byte-identical.
  This is the Derecho/DDS-style workload the stacked refactor targets.
* ``serve_fanout``  — the serve plane riding the substrate: G replica
  decode engines (a tiny real dense model) publishing every round's
  admissions + tokens through `Domain.bind`'s streamed stacked program
  (repro.serve.fanout.ReplicatedEngine).  ``cold_s`` includes the decode
  jit + the stream trace; ``warm_s`` (best-of-3, engines reset between
  runs) is the steady-state serve+multicast cost, with ``tok_per_s_warm``
  the wall-clock token rate and ``one_program`` asserting the whole run
  appended a single TRACE_EVENTS entry.
* ``serve_fused``   — the SAME serve workload as ``serve_fanout`` run as
  ONE device-resident program (repro.serve.fused): admission, decode,
  token emission, multicast publish, watermark-gated slot reuse and the
  settle drain all inside one ``lax.while_loop`` — ``host_hops`` must be
  0 (the unfused loop pays one logits readback per decode round plus one
  watermark view per push round) and a warm run must re-trace nothing.
* ``fused_saturation`` — the fused program scaled over replicas x slots
  at fixed per-slot work until wall-clock throughput saturates; the
  curve is the capacity story of the device-resident serve plane.
* ``view_change``   — warm reconfigure-under-traffic: the
  virtual-synchrony cut of a live stream (wedge + ragged trim + epoch
  carry + new-stream hand-off, DESIGN.md Sec. 7) with the padded stack
  shape preserved; ``reused_program`` asserts the new epoch dispatches
  the SAME cached program (no fresh-epoch restart), ``resend_msgs`` that
  traffic was genuinely in flight at the cut.
* ``slot_failure``  — warm reconfigure-with-slot-kill: a serve replica
  loses a publisher (slot) node mid-run; ``cut_s`` is the cut's own
  wall clock (wedge + dead-slot accounting + decode void/re-admit,
  DESIGN.md Secs. 7, 9) and ``reused_program`` asserts the shrunken
  sender set dispatches on the same cached stacked program.

Writes ``BENCH_hotpath.json`` at the repo root (committed — the perf
baseline later PRs regress against).  ``--smoke`` runs tiny shapes and
FAILS (exit 1) if wall-clock regresses >3x against the committed
baseline's ``smoke`` section (plus a small absolute slack so CI-machine
jitter can't flake it); this is the CI ``bench-smoke`` gate.

Run:  PYTHONPATH=src python benchmarks/hotpath.py [--smoke] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

from repro import use_compilation_cache
from repro.core.group import Group, single_group

ROOT = Path(__file__).resolve().parent.parent
BENCH_PATH = ROOT / "BENCH_hotpath.json"

# Wall clocks of the SAME scenarios measured at the parent commit
# (549ccb4, pre compile-once/vectorized-reconstruction), CPU backend.
# Kept as literals so the before/after story survives the refactor.
PRE_PR = {
    "graph_second_run_s": 0.473,
    "pallas_second_run_s": 0.718,
    "per_round_us_graph_second_run": 2543.2,
    "sequential_8_window_grid_s": 4.228,
    # the committed FULL serve_fanout row at the parent commit (per-round
    # dispatch loop, PR 7 baseline) — the fused serve plane's 5x target
    "serve_fanout_tok_per_s_warm": 487.6,
}

FULL = dict(n=8, senders=4, msgs=150, window=32)
FULL_GRID = (4, 8, 16, 24, 32, 48, 64, 100)
FULL_TOPICS = dict(n_nodes=8, n_topics=16, samples=40)
FULL_SERVE = dict(replicas=2, slots=3, reqs=5, prompt=4, new_tokens=6)
FULL_VC = dict(n=8, senders=4, window=8, rounds=6, per_round=2)
FULL_SLOTKILL = dict(replicas=2, slots=3, reqs=5, prompt=4,
                     new_tokens=6, fail_round=2)
SMOKE = dict(n=4, senders=2, msgs=24, window=8)
SMOKE_GRID = (4, 6, 8, 12)
SMOKE_TOPICS = dict(n_nodes=4, n_topics=16, samples=6)
SMOKE_SERVE = dict(replicas=2, slots=2, reqs=3, prompt=3, new_tokens=4)
SMOKE_VC = dict(n=4, senders=2, window=4, rounds=4, per_round=2)
SMOKE_SLOTKILL = dict(replicas=2, slots=2, reqs=3, prompt=3,
                      new_tokens=4, fail_round=2)

# --smoke regression gate: fail when current > 3x baseline + slack.  The
# slack absorbs CI-runner jitter on the millisecond-scale warm metrics but
# stays far below any real regression: a compile-once revert puts warm_s
# back at ~0.46s (the cold/trace cost), 9x over the 0.05s slack alone.
SMOKE_FACTOR = 3.0
SMOKE_SLACK_S = 0.05


def _scenario(n, senders, msgs, window):
    return single_group(n, n_senders=senders, msg_size=4096, window=window,
                        n_messages=msgs)


def bench_repeated_run(shape, backend="graph"):
    """Cold (trace+compile) vs warm (jit-cache hit) Group.run."""
    cfg = _scenario(**shape)
    t0 = time.perf_counter()
    Group(cfg).run(backend=backend)
    cold = time.perf_counter() - t0
    warm = float("inf")
    for _ in range(3):                       # best-of to de-noise CI boxes
        t0 = time.perf_counter()
        r = Group(cfg).run(backend=backend)
        warm = min(warm, time.perf_counter() - t0)
    per_node = r.delivered_app_msgs / max(len(r.per_node_throughput), 1)
    return {
        "cold_s": round(cold, 4),
        "warm_s": round(warm, 4),
        "speedup_cold_over_warm": round(cold / warm, 1),
        "rounds": r.rounds,
        "per_round_us_warm": round(warm / max(r.rounds, 1) * 1e6, 2),
        "per_msg_us_warm": round(warm / max(per_node, 1) * 1e6, 2),
    }


def _logs_identical(a, b):
    return (a.n_senders == b.n_senders
            and a.delivered_seq == b.delivered_seq
            and len(a.is_app) == len(b.is_app)
            and all(np.array_equal(x, y)
                    for x, y in zip(a.is_app, b.is_app)))


def bench_window_grid(shape, grid, backend="graph"):
    """One batched program vs len(grid) sequential runs, same results."""
    base = dict(shape)
    base.pop("window")
    t0 = time.perf_counter()
    seq_groups = []
    for w in grid:
        g = Group(_scenario(window=w, **base))
        g.run(backend=backend)
        seq_groups.append(g)
    sequential = time.perf_counter() - t0
    g = Group(_scenario(window=grid[0], **base))
    t0 = time.perf_counter()
    reports = g.run_batch(backend=backend, windows=list(grid))
    batched = time.perf_counter() - t0
    identical = all(
        _logs_identical(r.extras["delivery_logs"][gid], gi.delivery_logs[gid])
        for r, gi in zip(reports, seq_groups)
        for gid in gi.delivery_logs)
    return {
        "points": len(grid),
        "sequential_s": round(sequential, 4),
        "batch_s": round(batched, 4),
        "speedup_batch": round(sequential / batched, 1),
        "logs_identical": bool(identical),
    }


def bench_many_topics(shape, backend="graph"):
    """The many-subgroup dimension: one STACKED run of an n_topics-topic
    DDS domain vs n_topics sequential single-topic runs (both warm), with
    byte-identical per-topic delivery logs asserted."""
    from repro.core import dds

    def domain():
        return dds.many_topic_domain(shape["n_nodes"], shape["n_topics"],
                                     subscribers_per_topic=2,
                                     sample_size=4096, window=16)

    samples = shape["samples"]
    g = domain().group(samples_per_publisher=samples)
    t0 = time.perf_counter()
    g.run(backend=backend)
    cold = time.perf_counter() - t0
    warm = float("inf")
    for _ in range(3):
        g = domain().group(samples_per_publisher=samples)
        t0 = time.perf_counter()
        g.run(backend=backend)
        warm = min(warm, time.perf_counter() - t0)
    # sequential per-topic singles (each topic its own compiled program)
    def solos():
        from repro import api
        out = []
        cfg = g.cfg
        for spec in cfg.subgroups:
            out.append(api.Group(api.GroupConfig(
                members=spec.members, subgroups=(spec,), flags=cfg.flags)))
        return out

    for solo in solos():                     # warm every solo program
        solo.run(backend=backend)
    sequential = float("inf")                # best-of, like the stacked side
    for _ in range(3):
        seq_groups = solos()
        t0 = time.perf_counter()
        for solo in seq_groups:
            solo.run(backend=backend)
        sequential = min(sequential, time.perf_counter() - t0)
    identical = all(
        _logs_identical(g.delivery_logs[gid], solo.delivery_logs[0])
        for gid, solo in enumerate(seq_groups))
    return {
        "topics": shape["n_topics"],
        "cold_s": round(cold, 4),
        "stacked_warm_s": round(warm, 4),
        "sequential_warm_s": round(sequential, 4),
        "speedup_stacked": round(sequential / warm, 1),
        "logs_identical": bool(identical),
    }


_SERVE_ARCH = "hotpath-serve"


def _serve_engines(shape):
    """G fresh replica engines of a tiny REAL dense model (compiled decode
    is cached per engine; reset() between runs keeps it warm)."""
    import jax
    from repro.models import layers, registry
    from repro.models.config import ModelConfig
    from repro.models.runtime import Runtime
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg = ModelConfig(name=_SERVE_ARCH, family="dense", n_layers=2,
                      d_model=128, n_heads=4, n_kv_heads=2, d_ff=256,
                      vocab_size=512, head_dim=32, tie_embeddings=True)
    registry.register(_SERVE_ARCH, lambda: cfg)   # idempotent overwrite
    params = layers.init_tree(registry.param_specs(cfg), jax.random.key(0))
    return [ServeEngine(_SERVE_ARCH, params, cfg,
                        EngineConfig(max_batch=shape["slots"], max_len=64),
                        Runtime())
            for _ in range(shape["replicas"])], cfg


def bench_serve_fanout(shape, backend="graph"):
    """The serve plane on the stacked substrate: cold (decode jit + stream
    trace) vs warm engine-round loop, one compiled program per run."""
    from repro.core.group import TRACE_EVENTS
    from repro.serve.engine import Request
    from repro.serve.fanout import ReplicatedEngine

    engines, cfg = _serve_engines(shape)

    def run_once(rep):
        rep.reset()
        rng = np.random.default_rng(0)
        for g in range(shape["replicas"]):
            for i in range(shape["reqs"]):
                rep.submit(g, Request(
                    rid=g * 100 + i,
                    prompt=rng.integers(0, cfg.vocab_size, shape["prompt"],
                                        dtype=np.int32),
                    max_new_tokens=shape["new_tokens"]))
        t0 = time.perf_counter()
        report = rep.run()
        return time.perf_counter() - t0, report

    rep = ReplicatedEngine(engines, subscribers_per_replica=2, window=4,
                           backend=backend)
    n0 = len(TRACE_EVENTS)
    cold, report = run_once(rep)
    # at most one stacked trace for a whole run (0 if this scenario
    # shape's program is already cached in-process) — never one per
    # engine round or per replica topic
    one_program = (len(TRACE_EVENTS) - n0) <= 1
    warm, tok_s = float("inf"), 0.0
    for _ in range(3):
        w, report = run_once(rep)
        if w < warm:
            warm, tok_s = w, report.extras["serve"]["tokens_per_s"]
    serve = report.extras["serve"]
    return {
        "replicas": shape["replicas"],
        "slots": shape["slots"],
        "cold_s": round(cold, 4),
        "warm_s": round(warm, 4),
        "tok_per_s_warm": round(tok_s, 1),
        "tokens": serve["tokens"],
        "engine_rounds": serve["engine_rounds"],
        "rdma_writes": report.rdma_writes,
        "one_program": bool(one_program),
    }


def _fill_serve(rep, shape, cfg):
    rep.reset()
    rng = np.random.default_rng(0)
    for g in range(shape["replicas"]):
        for i in range(shape["reqs"]):
            from repro.serve.engine import Request
            rep.submit(g, Request(
                rid=g * 100 + i,
                prompt=rng.integers(0, cfg.vocab_size, shape["prompt"],
                                    dtype=np.int32),
                max_new_tokens=shape["new_tokens"]))


def bench_serve_fused(shape, backend="graph"):
    """The serve_fanout workload as ONE device-resident program: decode
    inside the scan body, zero host hops between rounds.  ``cold_s``
    includes tracing+compiling the fused while_loop; warm runs must hit
    the cached program (``warm_trace_events`` == 0) and report
    ``host_hops`` == 0 — the fused contract the CI smoke gate holds."""
    from repro.core.group import TRACE_EVENTS
    from repro.serve.fanout import ReplicatedEngine

    engines, cfg = _serve_engines(shape)
    rep = ReplicatedEngine(engines, subscribers_per_replica=2, window=4,
                           backend=backend)

    def run_once():
        _fill_serve(rep, shape, cfg)
        t0 = time.perf_counter()
        report = rep.run(fused=True)
        return time.perf_counter() - t0, report

    cold, report = run_once()
    n0 = len(TRACE_EVENTS)
    warm, tok_s = float("inf"), 0.0
    for _ in range(5):
        w, report = run_once()
        if w < warm:
            warm, tok_s = w, report.extras["serve"]["tokens"] / w
    serve = report.extras["serve"]
    return {
        "replicas": shape["replicas"],
        "slots": shape["slots"],
        "cold_s": round(cold, 4),
        "warm_s": round(warm, 4),
        "tok_per_s_warm": round(tok_s, 1),
        "tokens": serve["tokens"],
        "fused": bool(serve["fused"]),
        "fused_fallback": serve.get("fused_fallback"),
        "host_hops": serve["host_hops"],
        "engine_rounds": serve["engine_rounds"],
        "fused_rounds": serve.get("fused_rounds"),
        "warm_trace_events": len(TRACE_EVENTS) - n0,
    }


# fused saturation ladder: replicas x slots at fixed per-slot work
# (reqs = 2*slots keeps every point reusing each slot once)
SATURATION_LADDER = ((1, 2), (1, 4), (1, 8), (2, 4), (2, 8), (2, 16))


def bench_fused_saturation(ladder=SATURATION_LADDER):
    """Scale the fused program over replicas x slots until wall-clock
    throughput saturates.  Each point is a fresh compile (shape-static
    program) — ``cold_s`` is reported but the curve is ``tok_per_s_warm``
    over total slots."""
    from repro.serve.fanout import ReplicatedEngine

    curve = []
    for replicas, slots in ladder:
        shape = dict(replicas=replicas, slots=slots, reqs=2 * slots,
                     prompt=4, new_tokens=6)
        engines, cfg = _serve_engines(shape)
        rep = ReplicatedEngine(engines, subscribers_per_replica=2,
                               window=4)

        def run_once():
            _fill_serve(rep, shape, cfg)
            t0 = time.perf_counter()
            report = rep.run(fused=True)
            return time.perf_counter() - t0, report

        cold, _ = run_once()
        warm, report = float("inf"), None
        for _ in range(3):
            w, r = run_once()
            if w < warm:
                warm, report = w, r
        serve = report.extras["serve"]
        curve.append({
            "replicas": replicas,
            "slots": slots,
            "total_slots": replicas * slots,
            "tokens": serve["tokens"],
            "cold_s": round(cold, 4),
            "warm_s": round(warm, 4),
            "tok_per_s_warm": round(serve["tokens"] / warm, 1),
            "fused": bool(serve["fused"]),
        })
    peak = max(p["tok_per_s_warm"] for p in curve)
    return {
        "curve": curve,
        "peak_tok_per_s": peak,
        # saturated when the last doubling bought < 15% more throughput
        "saturated": bool(curve[-1]["tok_per_s_warm"] < 1.15
                          * curve[-2]["tok_per_s_warm"]),
    }


def bench_view_change(shape, backend="graph"):
    """Warm reconfigure-under-traffic: the virtual-synchrony cut of a
    LIVE stream (wedge at the SST watermarks, ragged trim, epoch carry,
    new-stream hand-off) with the padded stack shape preserved, so the
    cached one-round program is reused in the new epoch.  The measured
    wall clock is the cut itself — ``reused_program`` asserts the warm
    cycles never re-trace (a fresh-epoch-restart regression would show
    up both here and as a >3x reconfigure_s blowup)."""
    from repro import api
    from repro.core.group import TRACE_EVENTS

    n, s = shape["n"], shape["senders"]
    spec = api.SubgroupSpec(members=tuple(range(n)),
                            senders=tuple(range(s)), msg_size=4096,
                            window=shape["window"], n_messages=0)
    # one spare node outside the subgroup: its failure rolls the epoch
    # (full wedge + cut + resend) without re-shaping the stack
    cfg = api.GroupConfig(members=tuple(range(n + 1)), subgroups=(spec,))
    view = api.View(vid=1, members=tuple(range(n)),
                    senders=tuple(range(n)))

    def cycle():
        stream = api.Group(cfg).stream(backend=backend)
        ready = np.zeros(stream.shape, np.int32)
        ready[0, :s] = shape["per_round"]
        for _ in range(shape["rounds"]):
            stream.step(ready)
        t0 = time.perf_counter()
        s2 = stream.reconfigure(view)
        dt = time.perf_counter() - t0
        for _ in range(shape["rounds"]):
            s2.step(ready)
        report, _ = s2.finish()
        return dt, s2.carry, report

    cycle()                             # warm: trace the stream program
    n0 = len(TRACE_EVENTS)
    best, carry, report = float("inf"), None, None
    for _ in range(3):
        dt, c, r = cycle()
        if dt < best:
            best, carry, report = dt, c, r
    return {
        "reconfigure_s": round(best, 4),
        "resend_msgs": int(carry.total_resend()),
        "delivered_app_msgs": report.delivered_app_msgs,
        "reused_program": bool(len(TRACE_EVENTS) == n0),
    }


def bench_slot_failure(shape, backend="graph"):
    """Warm reconfigure-with-slot-kill: a serve replica loses a SLOT
    (publisher) node mid-run — wedge + cut + dead-slot accounting +
    in-flight decode voided and re-admitted on a surviving slot
    (DESIGN.md Secs. 7, 9).  ``cut_s`` is the cut's own wall clock
    (``ReplicatedEngine.cut_walls``); ``reused_program`` asserts the
    warm cycles never re-trace — the shrunken sender set dispatches on
    the SAME cached stacked program (padded S_max preserved by the
    surviving replica)."""
    from repro.core.group import TRACE_EVENTS
    from repro.serve.engine import Request
    from repro.serve.fanout import ReplicatedEngine

    engines, cfg = _serve_engines(shape)
    rep = ReplicatedEngine(engines, subscribers_per_replica=2, window=4,
                           backend=backend)
    kill = rep._slot_nodes[0][0]         # replica 0, slot 0

    def run_once():
        rep.reset()
        rng = np.random.default_rng(0)
        for g in range(shape["replicas"]):
            for i in range(shape["reqs"]):
                rep.submit(g, Request(
                    rid=g * 100 + i,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        shape["prompt"], dtype=np.int32),
                    max_new_tokens=shape["new_tokens"]))
        t0 = time.perf_counter()
        report = rep.run(fail_at={shape["fail_round"]: [kill]})
        return time.perf_counter() - t0, report

    cold, _ = run_once()
    n0 = len(TRACE_EVENTS)
    best_cut, warm, report = float("inf"), float("inf"), None
    for _ in range(3):
        w, r = run_once()
        if rep.cut_walls[0] < best_cut:
            best_cut, report = rep.cut_walls[0], r
        warm = min(warm, w)
    serve = report.extras["serve"]
    vc = rep.view_log[0][2].extras["view_change"]
    return {
        "replicas": shape["replicas"],
        "slots": shape["slots"],
        "cold_s": round(cold, 4),
        "warm_s": round(warm, 4),
        "cut_s": round(best_cut, 4),
        "resend_msgs": int(vc["resend_msgs"]),
        "slot_failures": serve["slot_failures"],
        "voided_requests": serve["voided_requests"],
        "requeued_requests": serve["requeued_requests"],
        "drained": bool(serve["drained"]),
        "reused_program": bool(len(TRACE_EVENTS) == n0),
    }


def run_suite(shape, grid, topics, serve, vc, slotkill):
    return {
        "repeated_run_graph": bench_repeated_run(shape, "graph"),
        "repeated_run_pallas": bench_repeated_run(shape, "pallas"),
        "window_grid_graph": bench_window_grid(shape, grid, "graph"),
        "many_topics_graph": bench_many_topics(topics, "graph"),
        "serve_fanout": bench_serve_fanout(serve, "graph"),
        "serve_fused": bench_serve_fused(serve, "graph"),
        "view_change": bench_view_change(vc, "graph"),
        "slot_failure": bench_slot_failure(slotkill, "graph"),
    }


def smoke_gate(baseline_path: Path) -> int:
    results = run_suite(SMOKE, SMOKE_GRID, SMOKE_TOPICS, SMOKE_SERVE,
                        SMOKE_VC, SMOKE_SLOTKILL)
    if not baseline_path.exists():
        print(f"no baseline at {baseline_path}; smoke measured only")
        print(json.dumps(results, indent=1))
        return 0
    base = json.loads(baseline_path.read_text()).get("smoke", {})
    failures = []
    for bench, metric in (("repeated_run_graph", "warm_s"),
                          ("repeated_run_pallas", "warm_s"),
                          ("window_grid_graph", "batch_s"),
                          ("many_topics_graph", "stacked_warm_s"),
                          ("serve_fanout", "warm_s"),
                          ("serve_fused", "warm_s"),
                          ("view_change", "reconfigure_s"),
                          ("slot_failure", "cut_s")):
        cur = results[bench][metric]
        ref = base.get(bench, {}).get(metric)
        if ref is None:
            continue
        limit = SMOKE_FACTOR * ref + SMOKE_SLACK_S
        status = "OK" if cur <= limit else "REGRESSION"
        print(f"{bench}.{metric}: {cur:.4f}s (baseline {ref:.4f}s, "
              f"limit {limit:.4f}s) {status}")
        if cur > limit:
            failures.append(bench)
    for bench in ("window_grid_graph", "many_topics_graph"):
        if not results[bench]["logs_identical"]:
            print(f"{bench}: batched/stacked logs DIVERGE from sequential")
            failures.append(f"{bench}.logs_identical")
    if not results["serve_fanout"]["one_program"]:
        print("serve_fanout: a run compiled more than one stacked program")
        failures.append("serve_fanout.one_program")
    sf = results["serve_fused"]
    if not sf["fused"]:
        print(f"serve_fused: fell back to the per-round loop "
              f"({sf['fused_fallback']})")
        failures.append("serve_fused.fused")
    if sf["host_hops"] != 0:
        print(f"serve_fused: {sf['host_hops']} host hops in a fused run "
              "(the device-resident contract is zero)")
        failures.append("serve_fused.host_hops")
    if sf["warm_trace_events"] > 1:
        print(f"serve_fused: warm runs appended "
              f"{sf['warm_trace_events']} TRACE_EVENTS entries "
              "(re-tracing per run)")
        failures.append("serve_fused.warm_trace_events")
    # relative throughput floor: fused must beat the per-round loop on
    # the SAME box and shape (absolute floors live in the full run's
    # acceptance, where the machine matches the committed baseline)
    if sf["tok_per_s_warm"] < 1.2 * results["serve_fanout"][
            "tok_per_s_warm"]:
        print(f"serve_fused: {sf['tok_per_s_warm']} tok/s is under 1.2x "
              f"the unfused loop "
              f"({results['serve_fanout']['tok_per_s_warm']} tok/s)")
        failures.append("serve_fused.tok_per_s_warm")
    if not results["view_change"]["reused_program"]:
        print("view_change: a shape-preserving cut re-traced the stream "
              "program (fresh-epoch restart regression)")
        failures.append("view_change.reused_program")
    if not results["slot_failure"]["reused_program"]:
        print("slot_failure: a slot-kill cut re-traced the stream "
              "program (fresh-epoch restart regression)")
        failures.append("slot_failure.reused_program")
    if not results["slot_failure"]["drained"]:
        print("slot_failure: the serve plane failed to drain after the "
              "slot kill")
        failures.append("slot_failure.drained")
    if failures:
        print(f"bench-smoke FAILED: {failures}")
        return 1
    print("bench-smoke passed")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes; fail on >3x regression vs baseline")
    ap.add_argument("--json", type=Path, default=BENCH_PATH)
    args = ap.parse_args()
    use_compilation_cache()
    if args.smoke:
        return smoke_gate(args.json)
    record = {
        "pre_pr_baseline": PRE_PR,
        "full": run_suite(FULL, FULL_GRID, FULL_TOPICS, FULL_SERVE,
                          FULL_VC, FULL_SLOTKILL),
        "smoke": run_suite(SMOKE, SMOKE_GRID, SMOKE_TOPICS, SMOKE_SERVE,
                           SMOKE_VC, SMOKE_SLOTKILL),
        "scenario": {"full": {**FULL, "grid": list(FULL_GRID),
                              "topics": dict(FULL_TOPICS),
                              "serve": dict(FULL_SERVE),
                              "view_change": dict(FULL_VC),
                              "slot_failure": dict(FULL_SLOTKILL)},
                     "smoke": {**SMOKE, "grid": list(SMOKE_GRID),
                               "topics": dict(SMOKE_TOPICS),
                               "serve": dict(SMOKE_SERVE),
                               "view_change": dict(SMOKE_VC),
                               "slot_failure": dict(SMOKE_SLOTKILL)},
                     "fused_saturation": [list(p) for p in
                                          SATURATION_LADDER]},
    }
    full = record["full"]
    record["fused_saturation"] = bench_fused_saturation()
    full["vs_pre_pr"] = {
        "graph_second_run_speedup": round(
            PRE_PR["graph_second_run_s"]
            / full["repeated_run_graph"]["warm_s"], 1),
        "pallas_second_run_speedup": round(
            PRE_PR["pallas_second_run_s"]
            / full["repeated_run_pallas"]["warm_s"], 1),
        "window_grid_speedup_vs_pre_pr_sequential": round(
            PRE_PR["sequential_8_window_grid_s"]
            / full["window_grid_graph"]["batch_s"], 1),
        "serve_fused_speedup_vs_pre_pr_serve_row": round(
            full["serve_fused"]["tok_per_s_warm"]
            / PRE_PR["serve_fanout_tok_per_s_warm"], 1),
    }
    args.json.write_text(json.dumps(record, indent=1) + "\n")
    print(json.dumps(record, indent=1))
    print(f"-> {args.json}")
    sat = record["fused_saturation"]
    ok = (full["repeated_run_graph"]["speedup_cold_over_warm"] >= 10
          and full["vs_pre_pr"]["graph_second_run_speedup"] >= 10
          and full["window_grid_graph"]["speedup_batch"] > 1
          and full["window_grid_graph"]["logs_identical"]
          and full["many_topics_graph"]["speedup_stacked"] > 1
          and full["many_topics_graph"]["logs_identical"]
          and full["serve_fanout"]["one_program"]
          and full["serve_fanout"]["tok_per_s_warm"] > 0
          and full["serve_fused"]["fused"]
          and full["serve_fused"]["host_hops"] == 0
          and full["serve_fused"]["warm_trace_events"] <= 1
          # the tentpole: >= 5x the committed per-round serve row at the
          # matched FULL_SERVE shape
          and full["vs_pre_pr"][
              "serve_fused_speedup_vs_pre_pr_serve_row"] >= 5.0
          and all(p["fused"] for p in sat["curve"])
          and sat["peak_tok_per_s"] >= full["serve_fused"][
              "tok_per_s_warm"]
          and full["view_change"]["reused_program"]
          and full["view_change"]["resend_msgs"] > 0
          and full["slot_failure"]["reused_program"]
          and full["slot_failure"]["drained"]
          and full["slot_failure"]["slot_failures"] == 1)
    print("acceptance:", "PASS" if ok else "FAIL")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
