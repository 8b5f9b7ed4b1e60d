"""Runner of a multicast deployment streamed round by round.

The timed path is the program's streaming entry point: one
``Group.stream(backend)`` stream, one ``GroupStream.step`` per protocol
round, each a dispatch of the compiled round program and the readback
of its results.  Before every round the traffic mix hands the stream
what arrived; the loop runs until ``--seconds`` have passed, then
drains with empty rounds until every published message is delivered.

What is compared (``checks``, each beside its limit 0):

* ``rounds_differ`` -- rounds in which what the program published and
  delivered differs from the plain reference (:mod:`benchlib.mcast_ref`)
  driven with the same arrivals;
* ``log_differ`` -- members whose delivered prefix, and senders whose
  app/null publish log, differ from the reference's after the drain;
* ``undelivered`` -- enqueued app messages that some member has not
  delivered after the drain (completeness).

Total order and per-sender FIFO hold by construction of the program's
delivery log: every member delivers a prefix of one round-robin
sequence, each sender's messages in publish-index order.  How far each
member got is ``log_differ``'s to check.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Dict

import numpy as np

from benchlib import harness, mcast_ref, rounds, trace as trace_mod
from benchlib.traffic import Offer

DRAIN_CAP = 5000          # rounds; a sound stream drains in tens


def _group_config(config: Dict[str, Any]):
    from repro import api

    flags = api.SpindleFlags.spindle()
    if not config["null_send"]:
        raise ValueError("only the paper's Spindle flags (null-send on) "
                         "are configured here")
    return api.single_group(int(config["n_nodes"]),
                            n_senders=int(config["n_senders"]),
                            msg_size=int(config["msg_size"]),
                            window=int(config["window"]), flags=flags)


def _own_share(prefix_len: int, n_senders: int) -> np.ndarray:
    ranks = np.arange(n_senders)
    return prefix_len // n_senders + (ranks < prefix_len % n_senders)


def _compare(log, ref: mcast_ref.MirrorRun, batches, app_pub, nulls,
             enqueued: np.ndarray, members) -> Dict[str, int]:
    """The numbers compared, each 0 on a correct run."""
    n_s = app_pub.shape[1]
    t_n = min(len(ref.batches), len(batches))
    differ = ((ref.batches[:t_n] != batches[:t_n]).any(axis=1)
              | (ref.app_pub[:t_n] != app_pub[:t_n]).any(axis=1)
              | (ref.nulls[:t_n] != nulls[:t_n]).any(axis=1))
    rounds_differ = int(differ.sum()) + abs(len(ref.batches)
                                            - len(batches))
    seqs = np.asarray([log.delivered_seq.get(m, -1) for m in members])
    log_differ = int((seqs != ref.delivered_num).sum())
    for s in range(n_s):
        mine = np.asarray(log.is_app[s], bool)
        if mine.shape != ref.is_app[s].shape or \
                not np.array_equal(mine, ref.is_app[s]):
            log_differ += 1
    # completeness: app messages every member delivered, per sender
    apps_cum = [np.concatenate([[0], np.cumsum(np.asarray(a, np.int64))])
                for a in log.is_app]
    everywhere = np.full(n_s, np.iinfo(np.int64).max)
    for seq in seqs:
        share = _own_share(int(seq) + 1, n_s)
        got = np.asarray([apps_cum[s][min(share[s], len(apps_cum[s]) - 1)]
                          for s in range(n_s)])
        everywhere = np.minimum(everywhere, got)
    undelivered = int(np.maximum(enqueued - everywhere, 0).sum())
    return {"rounds_differ": rounds_differ, "log_differ": log_differ,
            "undelivered": undelivered}


def drive(ctx) -> harness.Run:
    """Run one cell once.  ``ctx`` carries ``config``, ``mix``,
    ``seed``, ``seconds``, ``traced``, ``clock0`` (process start on the
    host clock), ``counter`` (a :class:`harness.CompileCounter`),
    ``devices``, ``trace_names`` (the trace's plane and line names, for
    a trace recorded on the CPU) and ``wrap_program`` (None, or a
    function that breaks the timed path -- the self-check's faults)."""
    import jax
    from repro import api

    config = ctx.config
    backend = config.get("backend", "graph")
    cfg = _group_config(config)
    n_members, n_s = int(config["n_nodes"]), int(config["n_senders"])
    window = int(config["window"])

    # warm-up: compile (or load) the round program and the host path
    warm = api.Group(cfg).stream(backend=backend)
    for k in range(4):
        warm.step(np.full((1, n_s), k, np.int32))
    del warm
    offer = Offer(ctx.mix, n_s, ctx.seed, float(ctx.seconds))
    stream = api.Group(cfg).stream(backend=backend)
    # the XLA module the stream dispatches once per round
    program_name = "jit_" + stream._program.__name__
    if ctx.wrap_program is not None:
        stream._program = ctx.wrap_program(stream._program)
    trace_dir = os.path.join(harness.OUT, "trace-" + ctx.workload)
    if ctx.traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=trace_mod.options())
    setup_s = time.perf_counter() - ctx.clock0
    compiles0 = ctx.counter.compiles

    ends, readies = [], []
    backlog = np.zeros(n_s, np.int64)
    ann = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    with ann("bench.window"):
        while True:
            now = time.perf_counter() - t0
            last = now >= ctx.seconds
            if last and offer.due is None:
                break
            with ann("bench.traffic"):
                ready = offer.take(now, backlog)
            with ann("bench.step"):
                view = stream.step(ready[None, :])
            ends.append(time.perf_counter() - t0)
            readies.append(ready)
            backlog = np.asarray(view.backlog[0, :n_s], np.int64)
            if last:
                break
    window_s = ends[-1]
    if ctx.traced:
        jax.profiler.stop_trace()
    in_window = ctx.counter.compiles - compiles0
    if in_window:
        raise RuntimeError(f"{in_window} programs compiled inside the "
                           "measured window")
    t_w = len(ends)

    # drain: empty rounds until every published message is delivered
    zeros = np.zeros((1, n_s), np.int32)
    prev = None
    while not stream.quiescent() and len(ends) - t_w < DRAIN_CAP:
        view = stream.step(zeros)
        ends.append(time.perf_counter() - t0)
        readies.append(np.zeros(n_s, np.int64))
        now_state = (view.delivered_num.tobytes(), view.published.tobytes(),
                     view.backlog.tobytes())
        if now_state == prev:
            break                       # a fixed point: nothing moves
        prev = now_state
    _, logs = stream.finish()
    peak = harness.memory_peak(ctx.devices)

    batches, app_pub, nulls = (x[0] for x in stream.traces())
    batches = batches[:, :n_members].astype(np.int64)
    app_pub = app_pub[:, :n_s].astype(np.int64)
    nulls = nulls[:, :n_s].astype(np.int64)
    t_all = len(ends)
    if batches.shape[0] != t_all:       # finish() stepped on its own
        ends.extend([ends[-1]] * (batches.shape[0] - t_all))
        readies.extend([np.zeros(n_s, np.int64)]
                       * (batches.shape[0] - t_all))
    ready_all = np.stack(readies)
    ends = np.asarray(ends)

    # every app message: the round it arrived in and was delivered in
    deliv = rounds.delivery_rounds(app_pub, nulls, batches)
    enq_cum = np.cumsum(ready_all, axis=0)
    enqueued = enq_cum[-1]
    t_end = batches.shape[0]
    attempted = int(enq_cum[t_w - 1].sum())
    delivered_win = sum(int((d < t_w).sum()) for d in deliv)
    failed = attempted - sum(
        int((d[: int(enq_cum[t_w - 1, s])] < t_end).sum())
        for s, d in enumerate(deliv))
    lat_ms, lat_rounds = [], []
    for s, d in enumerate(deliv):
        k = min(int(enq_cum[t_w - 1, s]), len(d))
        d = d[:k]
        ok = d < t_end
        arrived = np.searchsorted(enq_cum[:, s], np.arange(k),
                                  side="right")
        lat_rounds.append((d - arrived + 1)[ok])
        if offer.due is not None:
            lat_ms.append((ends[d[ok]] - offer.due[s][:k][ok]) * 1e3)
    values = {
        "window_rounds": t_w,
        "long_rounds": int((np.diff(ends[:t_w], prepend=0.0) > 0.05).sum()),
        "drain_rounds": t_end - t_w,
        "delivered_in_window": delivered_win,
        "app_published_in_window": int(app_pub[:t_w].sum()),
        "nulls_in_window": int(nulls[:t_w].sum()),
        "delivery_rounds": np.concatenate(lat_rounds),
        "latency_ms": (np.concatenate(lat_ms) if lat_ms else None),
        "lateness_s": offer.lateness(),
        "stream_program": program_name,
        "setup_compiles": compiles0,
        "setup_cache_hits": ctx.counter.cache_hits,
    }
    run = harness.Run(setup_s=setup_s, window_s=window_s,
                      attempted=attempted, failed=failed, checks={},
                      values=values, memory_peak_bytes=peak)
    if ctx.traced:
        run.trace = trace_mod.reduce_trace(trace_dir, **ctx.trace_names)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the reference, once the window is closed and its numbers are read
    t_ref = time.perf_counter()
    ref = mcast_ref.run_rounds(ready_all, n_members=n_members,
                               window=window,
                               null_send=bool(config["null_send"]))
    cmp = _compare(logs[0], ref, batches, app_pub, nulls, enqueued,
                   cfg.subgroups[0].members)
    run.checks = {k: (float(v), 0.0) for k, v in cmp.items()}
    values["reference_s"] = time.perf_counter() - t_ref
    return run
