#!/usr/bin/env python3
"""Drive the main path once on one TPU chip and check what comes out.

Phases, in order, in this one process (a chip belongs to one process):

  (a) device check: JAX must find a TPU, or the script exits non-zero;
  (b) paper testbed: 16 nodes, all senders, 10 KB messages, window 100,
      1000 messages each, through ``Group.run`` on ``graph`` and
      ``pallas``; every member's delivered sequence must equal the ``des``
      host simulator's, graph and pallas logs must be bit-identical, and
      the pallas program must hold the compiled kernel (``tpu_custom_call``);
  (c) streamed DDS: the avionics domain (one publisher, 15 subscribers)
      through ``Domain.bind`` and ``push_round`` on graph and pallas,
      checked against the same rounds streamed through ``des``;
  (d) batch grid: the Fig. 6 window grid through ``Group.run_batch``,
      checked against a sequential ``Group.run`` of each point;
  (e) serve plane: ``qwen1.5-0.5b`` at its published widths and full
      depth (random weights from ``--seed``), 2 replicas x 4 slots,
      through the fused ``ReplicatedEngine.run``; its tokens must equal
      the per-round loop's on the same engines.

``--four-chips`` runs only the path that exists across chips: the window
grid sharded over four devices by ``run_batch``, checked against
sequential single-chip runs.

Earlier lines report wall times (compile included, as set-up) and counts
on the host clock; none of them is a device metric.  The last line is
one JSON object naming the device.

Run:  python chip_smoke.py [--four-chips] [--seed N]
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# The paper's testbed (Spindle Sec. 4): 16 nodes, every node a sender.
TESTBED = dict(n_nodes=16, msg_size=10240, window=100, n_messages=1000)
# The Fig. 6 window sweep.
FIG6_WINDOWS = (4, 8, 16, 24, 32, 48, 64, 100)
BACKENDS = ("graph", "pallas")


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(n_chips: int):
    """Phase (a): the devices JAX found, or exit when they are no TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform!r}")
    if len(devices) < n_chips:
        sys.exit(f"chip_smoke: needs {n_chips} TPU chips; JAX found "
                 f"{len(devices)}")
    return devices


def check(ok: bool, what: str) -> None:
    """Fail the smoke (an ``assert`` would vanish under ``python -O``)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


def _logs_identical(a, b) -> bool:
    """Bit-for-bit equality of two {gid: DeliveryLog} maps."""
    import numpy as np

    return sorted(a) == sorted(b) and all(
        a[k].n_senders == b[k].n_senders
        and a[k].delivered_seq == b[k].delivered_seq
        and len(a[k].is_app) == len(b[k].is_app)
        and all(np.array_equal(x, y)
                for x, y in zip(a[k].is_app, b[k].is_app))
        for k in a)


def _pallas_program_text(cfg) -> str:
    """Lowered text of the scan program a pallas ``Group.run`` of ``cfg``
    dispatches (the same cached program, lowered again on its inputs)."""
    from repro import api
    from repro.core import group as group_mod

    g = api.Group(cfg)
    counts = {gid: g.send_counts(gid) for gid in range(g.n_subgroups)}
    program, args, _ = group_mod.get_backend("pallas").program(cfg, counts)
    return program.lower(*args).as_text()


def _per_sender(seq):
    """A member's delivered app sequence of (rank, publish index, is_app)
    reduced to rank -> app messages delivered from that sender, after
    checking each sender's messages arrive in publish (FIFO) order."""
    last, out = {}, {}
    for rank, idx, _ in seq:
        check(idx > last.get(rank, -1), f"sender {rank} out of FIFO order")
        last[rank] = idx
        out[rank] = out.get(rank, 0) + 1
    return out


def phase_testbed(n_nodes=16, msg_size=10240, window=100, n_messages=1000):
    """(b) The paper's testbed through Group.run on graph and pallas.

    Two host references, both independent of the device.  The ``des``
    backend's numpy round mirror, streamed the same schedule a scheduled
    run lowers to (one message per sender per round until its budget is
    spent), must give bit-identical logs.  The event-level ``des``
    simulator places its null messages by simulated RDMA timing, so its
    interleaving differs; against it every member must deliver every
    sender's messages, complete and in FIFO order."""
    import numpy as np

    from repro import api

    cfg = api.single_group(n_nodes, msg_size=msg_size, window=window,
                           n_messages=n_messages)
    members = cfg.subgroups[0].members
    total = n_nodes * n_nodes * n_messages
    t0 = time.perf_counter()
    event = api.Group(cfg)
    event_report = event.run(backend="des")
    want = {node: _per_sender(event.subgroup(0).delivered(node))
            for node in members}
    check(all(seqs == {r: n_messages for r in range(n_nodes)}
              for seqs in want.values()), "testbed des: incomplete")
    mirror = api.Group(cfg).stream(backend="des")
    counts = np.full(n_nodes, n_messages)
    for t in range(n_messages):
        mirror.step((t < counts)[None, :].astype(np.int32))
    _, mirror_logs = mirror.finish()
    log(f"testbed des: event simulator ({event_report.nulls_sent} nulls) "
        f"and round mirror in {time.perf_counter() - t0:.3f} s")
    for backend in BACKENDS:
        g = api.Group(cfg)
        t0 = time.perf_counter()
        g.run(backend=backend)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()
        report = api.Group(cfg).run(backend=backend)
        warm = time.perf_counter() - t0
        check(_logs_identical(g.delivery_logs, mirror_logs),
              f"testbed {backend}: logs differ from the des round mirror")
        got = {node: _per_sender(g.subgroup(0).delivered(node))
               for node in members}
        check(got == want, f"testbed {backend}: delivery differs from des")
        check(report.delivered_app_msgs == total,
              f"testbed {backend}: {report.delivered_app_msgs} deliveries")
        log(f"testbed {backend}: {report.delivered_app_msgs} deliveries "
            f"in {report.rounds} rounds, {report.nulls_sent} nulls; wall "
            f"cold {cold:.3f} s (compile included), warm {warm:.3f} s")
    check("tpu_custom_call" in _pallas_program_text(cfg),
          "testbed: the pallas program holds no compiled kernel")
    log("testbed: graph == pallas == des round mirror, bit for bit; every "
        "member delivers what des delivers; pallas program holds "
        "tpu_custom_call")


def phase_stream(n_nodes=16, n_subscribers=15, rounds=300, seed=0):
    """(c) The avionics DDS domain streamed round by round."""
    import numpy as np

    from repro import api

    domain = api.single_topic_domain(n_nodes, n_subscribers)
    topic = domain.topics[0]
    rng = np.random.default_rng(seed)
    plan = rng.integers(0, 4, size=(rounds, len(topic.publishers)))
    results = {}
    for backend in ("des",) + BACKENDS:
        bound = domain.bind(backend=backend)
        t0 = time.perf_counter()
        for row in plan:
            bound.push_round({topic.name: row})
        report, logs = bound.finish()
        results[backend] = (report, logs)
        log(f"stream {backend}: {rounds} rounds, "
            f"{report.delivered_app_msgs} deliveries; wall "
            f"{time.perf_counter() - t0:.3f} s")
    want = n_subscribers + 1
    check(results["des"][0].delivered_app_msgs == want * int(plan.sum()),
          "stream des: deliveries lost")
    for backend in BACKENDS:
        report, logs = results[backend]
        check(report.delivered_app_msgs
              == results["des"][0].delivered_app_msgs,
              f"stream {backend}: delivered counts differ from des")
        check(_logs_identical(logs, results["des"][1]),
              f"stream {backend}: topic logs differ from des")
    log("stream: graph == pallas == des")


def phase_grid(n_nodes=16, msg_size=10240, n_messages=1000,
               windows=FIG6_WINDOWS, want_devices=1):
    """(d) The Fig. 6 window grid as one run_batch program per backend,
    checked against sequential Group.run of each point (on one chip;
    sharded ``want_devices`` ways by run_batch)."""
    from repro import api

    cfg = api.single_group(n_nodes, msg_size=msg_size,
                           n_messages=n_messages)
    for backend in BACKENDS:
        t0 = time.perf_counter()
        reports = api.Group(cfg).run_batch(backend=backend,
                                           windows=list(windows))
        batch = time.perf_counter() - t0
        t0 = time.perf_counter()
        for w, rb in zip(windows, reports):
            check(rb.extras["batch_devices"] == want_devices,
                  f"grid {backend} w={w}: ran on "
                  f"{rb.extras['batch_devices']} devices")
            spec = dataclasses.replace(cfg.subgroups[0], window=w)
            g = api.Group(dataclasses.replace(cfg, subgroups=(spec,)))
            ri = g.run(backend=backend)
            check((rb.delivered_app_msgs, rb.nulls_sent, rb.rounds)
                  == (ri.delivered_app_msgs, ri.nulls_sent, ri.rounds)
                  and _logs_identical(rb.extras["delivery_logs"],
                                      g.delivery_logs),
                  f"grid {backend} w={w}: run_batch differs from Group.run")
        log(f"grid {backend}: {len(windows)} points on {want_devices} "
            f"device(s); run_batch wall {batch:.3f} s, sequential wall "
            f"{time.perf_counter() - t0:.3f} s (compiles included)")
    log("grid: run_batch == sequential Group.run at every point")


def serve_setup(arch="qwen1.5-0.5b", cfg=None, replicas=2, slots=4,
                max_len=512, n_requests=8, prompt_len=(64, 128),
                new_tokens=16, seed=0):
    """A ``ReplicatedEngine`` over replicas sharing one params tree drawn
    from ``seed``, and a ``submit()`` that resets it and queues the same
    seeded requests."""
    import jax
    import numpy as np

    from repro.models import layers, registry
    from repro.serve.engine import EngineConfig, Request, ServeEngine
    from repro.serve.fanout import ReplicatedEngine

    cfg = registry.get(arch).cfg if cfg is None else cfg
    specs = registry.param_specs(cfg)
    t0 = time.perf_counter()
    params = jax.jit(lambda key: layers.init_tree(specs, key))(
        jax.random.key(seed))
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"serve {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}, {n_params} parameters initialised in "
        f"{time.perf_counter() - t0:.3f} s")
    engines = [ServeEngine(arch, params, cfg,
                           EngineConfig(max_batch=slots, max_len=max_len))
               for _ in range(replicas)]
    rep = ReplicatedEngine(engines, subscribers_per_replica=2, window=4)
    rng = np.random.default_rng(seed)
    requests = [(i % replicas, i,
                 rng.integers(1, cfg.vocab_size,
                              size=int(rng.integers(prompt_len[0],
                                                    prompt_len[1] + 1)))
                 .astype(np.int32))
                for i in range(n_requests)]

    def submit():
        rep.reset()
        for g, rid, prompt in requests:
            rep.submit(g, Request(rid=rid, prompt=prompt.copy(),
                                  max_new_tokens=new_tokens))

    return rep, submit


def phase_serve(n_requests=8, new_tokens=16, **kw):
    """(e) A replicated serve run, fused, against the per-round loop."""
    rep, submit = serve_setup(n_requests=n_requests, new_tokens=new_tokens,
                              **kw)
    submit()
    t0 = time.perf_counter()
    fused = rep.run(fused=True)
    log(f"serve fused: wall {time.perf_counter() - t0:.3f} s "
        "(compile included)")
    sf = fused.extras["serve"]
    check(sf["fused"] is True and "fused_fallback" not in sf,
          f"serve: fell back ({sf.get('fused_fallback')})")
    check(sf["host_hops"] == 0, f"serve: {sf['host_hops']} host hops")
    check(sf["drained"] and sf["requests"] == n_requests
          and sf["tokens"] == n_requests * new_tokens,
          f"serve: {sf['requests']} requests, {sf['tokens']} tokens")
    fused_tokens = rep.completed()
    submit()
    t0 = time.perf_counter()
    loop = rep.run()
    log(f"serve per-round loop: wall {time.perf_counter() - t0:.3f} s "
        f"(compile included), {loop.extras['serve']['host_hops']} host "
        "hops")
    check(rep.completed() == fused_tokens,
          "serve: fused tokens differ from the per-round loop's")
    check(loop.delivered_app_msgs == fused.delivered_app_msgs,
          "serve: fused deliveries differ from the per-round loop's")
    first = fused_tokens[0][0]
    log(f"serve: {sf['tokens']} tokens over {sf['engine_rounds']} engine "
        f"rounds, fused == per-round loop; replica 0 request 0 -> "
        f"{first[:8]}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only run_batch sharded over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    n_chips = 4 if args.four_chips else 1
    devices = require_tpu(n_chips)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro

    log(f"compile cache: {repro.use_compilation_cache()}")
    dev = devices[0]
    log(f"devices: {len(devices)} x {dev.device_kind}")
    t_all = time.perf_counter()
    if args.four_chips:
        phase_grid(want_devices=n_chips)
    else:
        phase_testbed(**TESTBED)
        phase_stream(seed=args.seed)
        phase_grid()
        phase_serve(seed=args.seed)
    stats = dev.memory_stats() or {}
    log(f"all phases passed in {time.perf_counter() - t_all:.3f} s; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
