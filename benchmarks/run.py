"""Benchmark harness — one function per paper table/figure, driven through
the unified Group API (every scenario is a GroupConfig run on the ``des``
backend; the ``backends`` bench runs one scenario across all three).

Prints ``name,us_per_call,derived`` CSV (us_per_call = simulated mean
per-message delivery interval at one node; derived = the figure's headline
metric, GB/s unless noted).  Full records land in results/bench/*.json.

Run:  PYTHONPATH=src python -m benchmarks.run [--only fig3 ...]
"""

from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

from repro import use_compilation_cache
from repro.core import costmodel, dds, simulator as sim
from repro.core.group import Group, RunReport, single_group

RESULTS = Path("results/bench")
_ROWS = []
_CACHE = {}


def emit(name: str, us_per_call: float, derived: float, **extra):
    _ROWS.append({"name": name, "us_per_call": round(us_per_call, 3),
                  "derived": round(derived, 4), **extra})
    print(f"{name},{us_per_call:.3f},{derived:.4f}", flush=True)


def run_group(make_group, key: str) -> RunReport:
    """Run ``make_group()`` on the des backend of the Group API (cached)."""
    if key not in _CACHE:
        _CACHE[key] = make_group().run(backend="des")
    return _CACHE[key]


def run_sim(cfg: sim.SimConfig, key: str) -> RunReport:
    return run_group(lambda: Group.from_sim_config(cfg), key)


def _per_msg_us(r: RunReport) -> float:
    if r.delivered_app_msgs == 0:
        return float("inf")
    per_node = r.delivered_app_msgs / max(len(r.per_node_throughput), 1)
    return r.duration_us / max(per_node, 1)


def _flags(**kw) -> sim.SpindleFlags:
    return sim.SpindleFlags(**kw)


BASE_N = dict(spindle=1200, baseline=250)


def _single(n, *, senders=None, flags=None, msgs=None, **kw):
    label = "baseline" if flags and not flags.batch_send and \
        not flags.batch_receive else "spindle"
    flags = flags if flags is not None else sim.SpindleFlags.spindle()
    msgs = msgs if msgs is not None else BASE_N[label]
    return sim.single_subgroup(n, n_senders=senders, n_messages=msgs,
                               flags=flags, **kw)


# ---------------------------------------------------------------------------

def fig1_latency_curve():
    """RDMA write latency vs size (cost-model calibration, Fig. 1)."""
    for size in (1, 128, 1024, 4096, 10240):
        lat = costmodel.RDMA_CX6.wire_latency(min(size, 4096)) + \
            costmodel.RDMA_CX6.serialization(size)
        emit(f"fig1/latency_{size}B", lat, lat)


def fig3_single_subgroup():
    """Single subgroup continuous sending, 10KB (Fig. 3): baseline vs
    opportunistic batching across group sizes and sender fractions."""
    for n in (2, 4, 8, 11, 16):
        for mode, senders in (("all", None), ("half", max(n // 2, 1)),
                              ("one", 1)):
            r = run_sim(_single(n, senders=senders),
                        f"spin_{n}_{mode}")
            emit(f"fig3/spindle_n{n}_{mode}", _per_msg_us(r),
                 r.throughput_GBps)
    for n in (2, 8, 16):
        r = run_sim(_single(n, flags=sim.SpindleFlags.baseline()),
                    f"base_{n}_all")
        emit(f"fig3/baseline_n{n}_all", _per_msg_us(r),
             r.throughput_GBps)


def fig4_delivery_rate():
    """Messages delivered per second across small sizes (Fig. 4)."""
    for size in (1, 128, 1024, 10240):
        r = run_sim(_single(16, msg_size=size, msgs=800),
                    f"size_{size}")
        rate = r.delivered_app_msgs / max(len(r.per_node_throughput), 1) \
            / max(r.duration_us, 1e-9) * 1e6
        emit(f"fig4/rate_{size}B", _per_msg_us(r), rate,
             throughput_GBps=r.throughput_GBps)


def fig5_incremental_stages():
    """Batching applied to successively more stages (Fig. 5), n=16."""
    stages = [
        ("baseline", sim.SpindleFlags.baseline()),
        ("+delivery", sim.SpindleFlags(
            batch_receive=False, batch_send=False, null_send=False,
            early_lock_release=False, batched_upcall=True)),
        ("+receive", sim.SpindleFlags(
            batch_send=False, null_send=False, early_lock_release=False)),
        ("+send", sim.SpindleFlags(null_send=False,
                                   early_lock_release=False)),
        ("+nulls", sim.SpindleFlags(early_lock_release=False)),
        ("+locks", sim.SpindleFlags.spindle()),
    ]
    for name, flags in stages:
        msgs = 250 if name == "baseline" else 800
        r = run_sim(_single(16, flags=flags, msgs=msgs), f"stage_{name}")
        emit(f"fig5/{name}", r.mean_latency_us, r.throughput_GBps,
             latency_us=r.mean_latency_us)


def fig6_window_size():
    """Ring-buffer window sweep (Fig. 6), all senders, n=16 — the whole
    grid executes as ONE compiled batched program (Group.run_batch on the
    graph substrate) instead of 5 sequential runs."""
    wins = (5, 20, 100, 500, 1000)
    g = Group(single_group(16, msg_size=10240, window=100, n_messages=800))
    for w, r in zip(wins, g.run_batch(backend="graph", windows=list(wins))):
        emit(f"fig6/w{w}", _per_msg_us(r), r.throughput_GBps)


def fig7_batch_histograms():
    """Batch-size distributions per stage (Fig. 7), n=16 all senders."""
    r = run_sim(_single(16), "spin_16_all")
    for stage, data in (("send", r.send_batches),
                        ("receive", r.recv_batches),
                        ("delivery", r.deliv_batches)):
        arr = np.asarray(data)
        emit(f"fig7/{stage}_mean", float(arr.mean()), float(arr.mean()),
             p50=float(np.percentile(arr, 50)),
             p95=float(np.percentile(arr, 95)))


def _multi_group(n_nodes, n_groups, active, flags, msgs):
    groups = []
    for g in range(n_groups):
        groups.append(sim.SubgroupSpec(
            members=tuple(range(n_nodes)), senders=tuple(range(n_nodes)),
            n_messages=msgs if (g == 0 or active == "all") else 0))
    return sim.SimConfig(n_nodes=n_nodes, subgroups=tuple(groups),
                         flags=flags)


def fig9_single_active_subgroup():
    """1 active subgroup among k overlapping (Figs. 8/9)."""
    for k in (1, 2, 5, 10, 20):
        r = run_sim(_multi_group(16, k, "one",
                                 sim.SpindleFlags.spindle(), 700),
                    f"act1_spin_{k}")
        emit(f"fig9/spindle_groups{k}", _per_msg_us(r),
             r.throughput_GBps)
    for k in (1, 2, 5, 10):
        r = run_sim(_multi_group(16, k, "one",
                                 sim.SpindleFlags.baseline(), 120),
                    f"act1_base_{k}")
        emit(f"fig8/baseline_groups{k}", _per_msg_us(r),
             r.throughput_GBps)


def fig10_delayed_sender():
    """Null-sends under sender delays (Fig. 10)."""
    cases = [("one_1us", 1, 1.0), ("one_100us", 1, 100.0),
             ("one_inf", 1, 1e9), ("half_1us", 8, 1.0),
             ("half_100us", 8, 100.0), ("half_inf", 8, 1e9)]
    for name, k, delay in cases:
        pats = tuple(((0, i), sim.SenderPattern(inter_send_delay_us=delay))
                     for i in range(k))
        cfg = sim.single_subgroup(
            16, n_messages=4000, patterns=pats,
            target_delivered=(16 - k) * 700)
        r = run_sim(cfg, f"delay_{name}")
        emit(f"fig10/{name}", _per_msg_us(r), r.throughput_GBps,
             nulls=r.nulls_sent)


def fig11_null_overhead():
    """Null-send overhead under continuous sending (Fig. 11).  Per group
    size the on/off pair runs as ONE batched program (Group.run_batch over
    the null_send flag grid on the graph substrate)."""
    for n in (2, 4, 8, 16):
        g = Group(single_group(n, msg_size=10240, window=100,
                               n_messages=1200))
        r_on, r_off = g.run_batch(backend="graph",
                                  null_send=[True, False])
        emit(f"fig11/nulls_on_n{n}", _per_msg_us(r_on),
             r_on.throughput_GBps, nulls=r_on.nulls_sent)
        emit(f"fig11/nulls_off_n{n}", _per_msg_us(r_off),
             r_off.throughput_GBps)


def fig12_thread_sync():
    """Lock release before RDMA posts (Fig. 12)."""
    for n in (4, 8, 16):
        r_on = run_sim(_single(n), f"spin_{n}_all")
        r_off = run_sim(_single(n, flags=_flags(early_lock_release=False),
                                msgs=1200), f"nolock_{n}")
        emit(f"fig12/locks_early_n{n}", _per_msg_us(r_on),
             r_on.throughput_GBps)
        emit(f"fig12/locks_held_n{n}", _per_msg_us(r_off),
             r_off.throughput_GBps)


def fig13_multi_active():
    """Multiple active subgroups with all optimizations (Fig. 13)."""
    for k in (1, 2, 5):
        r = run_sim(_multi_group(16, k, "all",
                                 sim.SpindleFlags.spindle(), 400),
                    f"actall_spin_{k}")
        emit(f"fig13/spindle_active{k}", _per_msg_us(r),
             r.throughput_GBps)


def fig14_memcpy_curve():
    """Host memcpy latency vs size (Fig. 14 calibration)."""
    for size in (128, 1024, 10240, 102400):
        lat = costmodel.HOST_X86.memcpy(size)
        emit(f"fig14/memcpy_{size}B", lat, size / max(lat, 1e-9) / 1e3)


def fig15_memcpy_delivery():
    """memcpy in send + delivery paths (Fig. 15), n=16."""
    for mode, flags in (
            ("zero_copy", sim.SpindleFlags.spindle()),
            ("memcpy", _flags(memcpy_delivery=True, memcpy_send=True))):
        r = run_sim(_single(16, flags=flags, msgs=800), f"memcpy_{mode}")
        emit(f"fig15/{mode}", _per_msg_us(r), r.throughput_GBps)


def fig16_final():
    """Final throughput + latency, all optimizations (Figs. 16/17)."""
    for n in (2, 8, 16):
        for mode, senders in (("all", None), ("half", max(n // 2, 1)),
                              ("one", 1)):
            r = run_sim(_single(n, senders=senders),
                        f"spin_{n}_{mode}")
            emit(f"fig16/n{n}_{mode}", r.mean_latency_us,
                 r.throughput_GBps, p99_latency_us=r.p99_latency_us)


def fig18_dds_qos():
    """DDS QoS levels, baseline vs Spindle (Fig. 18)."""
    for qos in dds.QoS:
        for spindle in (False, True):
            domain = dds.single_topic_domain(16, 15, qos=qos)
            r = run_group(lambda: domain.group(
                samples_per_publisher=150 if not spindle else 800,
                spindle=spindle), f"dds_{qos.value}_{spindle}")
            tag = "spindle" if spindle else "baseline"
            emit(f"fig18/{qos.value}_{tag}", _per_msg_us(r),
                 r.throughput_GBps)


def backends_cross_substrate():
    """One GroupConfig scenario on all three protocol backends — the
    unified-API like-for-like comparison (des vs graph vs pallas).  The
    graph/pallas points go through the batched execution path
    (Group.run_batch), which is asserted to reproduce Group.run exactly."""
    cfg = single_group(8, n_senders=4, msg_size=4096, window=32,
                       n_messages=60)
    seqs = {}
    g = Group(cfg)
    r = g.run(backend="des")
    seqs["des"] = g.subgroup(0).delivered(0)
    emit("backends/des", _per_msg_us(r), r.throughput_GBps,
         rdma_writes=r.rdma_writes, nulls=r.nulls_sent,
         delivered_app=r.delivered_app_msgs, stalled=r.stalled)
    for backend in ("graph", "pallas"):
        g = Group(cfg)
        (r,) = g.run_batch(backend=backend, windows=[32])
        log = r.extras["delivery_logs"][0]
        seqs[backend] = log.sequence(0)
        r_single = Group(cfg).run(backend=backend)
        assert r_single.delivered_app_msgs == r.delivered_app_msgs, backend
        emit(f"backends/{backend}", _per_msg_us(r), r.throughput_GBps,
             rdma_writes=r.rdma_writes, nulls=r.nulls_sent,
             delivered_app=r.delivered_app_msgs, stalled=r.stalled)
    agree = seqs["des"] == seqs["graph"] == seqs["pallas"]
    emit("backends/delivery_order_identical", 0.0, float(agree))
    assert agree, "backends disagree on the delivered total order"


def sec35_upcall_delay():
    """Sensitivity to delivery-upcall delay (Sec. 3.5)."""
    base = None
    for delay in (0.0, 1.0, 100.0, 1000.0):
        flags = _flags(batched_upcall=False)
        cfg = sim.single_subgroup(16, n_messages=300, flags=flags,
                                  upcall_extra_us=delay)
        r = run_sim(cfg, f"upcall_{delay}")
        if base is None:
            base = r.throughput_GBps
        emit(f"sec35/upcall_{int(delay)}us", _per_msg_us(r),
             r.throughput_GBps,
             frac_of_no_delay=r.throughput_GBps / max(base, 1e-9))


def gradsync_collectives():
    """Training-plane analogue: collectives per step for per-tensor vs
    fused-bucket vs compressed gradient multicast (analytic, from the
    bucket plan of the examples/train_lm 100M model)."""
    import jax
    import sys
    sys.path.insert(0, ".")
    from examples.train_lm import model_100m
    from repro.core import gradsync
    from repro.models import registry as reg
    from repro.models import layers as L

    cfg = model_100m()
    specs = reg.param_specs(cfg)
    abstract = L.abstract_tree(specs)
    n_tensors = len(jax.tree.leaves(abstract))
    total_bytes = float(sum(
        np.prod(l.shape, dtype=np.int64) * 4
        for l in jax.tree.leaves(abstract)))
    plan = gradsync.make_plan(abstract, target_bytes=32 << 20)
    g = 16  # DP degree
    ar = lambda b: 2 * (g - 1) / g * b  # noqa: E731  ring all-reduce
    compressed_wire = (g - 1) / g * total_bytes + \
        (g - 1) * (total_bytes / 4 / g)   # RS f32 + AG int8
    emit("gradsync/per_tensor", float(n_tensors), ar(total_bytes) / 1e9,
         collectives=n_tensors)
    emit("gradsync/fused", float(plan.n_buckets), ar(total_bytes) / 1e9,
         collectives=plan.n_buckets)
    emit("gradsync/compressed", float(plan.n_buckets),
         compressed_wire / 1e9, collectives=2 * plan.n_buckets)


BENCHES = {
    "fig1": fig1_latency_curve,
    "fig3": fig3_single_subgroup,
    "fig4": fig4_delivery_rate,
    "fig5": fig5_incremental_stages,
    "fig6": fig6_window_size,
    "fig7": fig7_batch_histograms,
    "fig9": fig9_single_active_subgroup,
    "fig10": fig10_delayed_sender,
    "fig11": fig11_null_overhead,
    "fig12": fig12_thread_sync,
    "fig13": fig13_multi_active,
    "fig14": fig14_memcpy_curve,
    "fig15": fig15_memcpy_delivery,
    "fig16": fig16_final,
    "fig18": fig18_dds_qos,
    "sec35": sec35_upcall_delay,
    "gradsync": gradsync_collectives,
    "backends": backends_cross_substrate,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args()
    use_compilation_cache()
    names = args.only if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    t0 = time.time()
    for name in names:
        BENCHES[name]()
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "bench.json").write_text(json.dumps(_ROWS, indent=1))
    print(f"# {len(_ROWS)} rows in {time.time()-t0:.0f}s "
          f"-> {RESULTS/'bench.json'}")


if __name__ == "__main__":
    main()
