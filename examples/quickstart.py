"""Quickstart: the Spindle techniques in 90 seconds — through the unified
Derecho-style Group API.

1. One `GroupConfig` scenario, run like-for-like on the calibrated DES:
   baseline Derecho vs Spindle (opportunistic batching + null-sends +
   lock restructuring).
2. The null-send scheme absorbing a delayed sender.
3. The SAME scenario on the in-graph (`graph`) and Pallas-kernel
   (`pallas`) backends — one config, three substrates, one RunReport.
4. Fuse gradient buckets with the same opportunistic-batching idea.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import numpy as np

from repro import api, use_compilation_cache
from repro.core import gradsync


def protocol_demo():
    print("=== 1. atomic multicast, 16 nodes, 10KB messages (Group API) ===")
    base = api.Group(api.single_group(
        16, n_messages=300, flags=api.SpindleFlags.baseline())).run("des")
    spin = api.Group(api.single_group(16, n_messages=1000)).run("des")
    print(f"  baseline : {base.throughput_GBps:6.2f} GB/s   "
          f"latency {base.mean_latency_us/1e3:7.2f} ms   "
          f"{base.rdma_writes} writes")
    print(f"  spindle  : {spin.throughput_GBps:6.2f} GB/s   "
          f"latency {spin.mean_latency_us/1e3:7.2f} ms   "
          f"{spin.rdma_writes} writes")
    print(f"  speedup  : {spin.throughput_GBps/base.throughput_GBps:.1f}x")


def nullsend_demo():
    print("=== 2. null-sends: one sender delayed 100us per message ===")
    pats = (((0, 3), api.SenderPattern(inter_send_delay_us=100.0)),)
    on = api.Group(api.single_group(
        16, n_messages=3000, patterns=pats,
        target_delivered=15 * 500)).run("des")
    off = api.Group(api.single_group(
        16, n_messages=3000, flags=api.SpindleFlags(null_send=False),
        patterns=pats, target_delivered=15 * 500)).run("des")
    print(f"  with nulls   : {on.throughput_GBps:6.2f} GB/s "
          f"({on.nulls_sent} nulls sent, "
          f"{on.delivered_null_msgs} null deliveries)")
    print(f"  without      : {off.throughput_GBps:6.2f} GB/s "
          f"(round-robin delivery stalls behind the laggard)")


def backend_demo():
    print("=== 3. one scenario, three substrates ===")
    cfg = api.single_group(4, n_senders=3, msg_size=1024, window=16,
                           n_messages=25)
    seqs = {}
    for backend in ("des", "graph", "pallas"):
        g = api.Group(cfg)
        r = g.run(backend=backend)
        seqs[backend] = g.subgroup(0).delivered(0)
        print(f"  {backend:<7}: {r.delivered_app_msgs} app deliveries, "
              f"{r.nulls_sent} nulls, {r.rdma_writes} writes, "
              f"{r.mean_latency_us:.1f} us mean latency")
    agree = seqs["des"] == seqs["graph"] == seqs["pallas"]
    print(f"  delivered total order identical on all backends: {agree}")


def gradsync_demo():
    print("=== 4. opportunistic gradient-bucket fusion ===")
    grads = {f"layer{i}": jax.numpy.ones((64, 128)) * i for i in range(20)}
    plan = gradsync.make_plan(grads, target_bytes=256 * 1024)
    n_tensors = len(jax.tree.leaves(grads))
    print(f"  {n_tensors} gradient tensors -> {plan.n_buckets} fused "
          f"collectives "
          f"(sizes: {[plan.bucket_bytes(b)//1024 for b in range(plan.n_buckets)]} KiB)")
    fused = gradsync.fused_psum_mean  # one psum per bucket inside shard_map
    print(f"  reduction entry point: {fused.__name__} "
          f"(see repro.train.steps for the shard_map wiring)")


if __name__ == "__main__":
    use_compilation_cache()
    protocol_demo()
    nullsend_demo()
    backend_demo()
    gradsync_demo()
