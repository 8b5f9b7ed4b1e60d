"""Fused device-resident serve plane: decode inside the scan body, one
compiled program per serve EPOCH (DESIGN.md Sec. 6).

The paper's core lesson is that small-object replication amplifies every
per-operation overhead until coordination is batched into the data path.
The unfused serve plane still pays that overhead once per engine round:
one jitted decode dispatch, a device->host logits sync, Python
bookkeeping, then one stacked-sweep dispatch
(:meth:`repro.serve.fanout.ReplicatedEngine.run`).  This module removes
the hop entirely: a whole serve run — open-loop arrivals, admission
(queue-cap tail-drop + backlog stalls), prefill, decode, token emission,
multicast publish, watermark-gated slot reuse, the quiescence drain —
executes as ONE compiled ``lax.while_loop`` program whose round body
composes the engine's masked decode step
(:meth:`repro.serve.engine.ServeEngine` ``_decode_body``) with the
multicast round body (:func:`repro.core.sweep.stream_stacked`).  Slot
state, decode caches, SST watermarks, backlogs, slot holds, the arrival
frontier and the admission queue all live in the carry; per-round event
traces land in preallocated device buffers and cross to the host exactly
once, after the loop exits.

Dynamic workloads ride in-graph (this retired the PR 8 fallbacks):

* **open-loop arrivals** — a seeded schedule is a host-precomputed
  per-round arrival-count matrix; the carry tracks the arrival frontier
  (``avail``) and admission gates on it.  Only an *arbitrary*
  ``arrive_fn`` callable still falls back.
* **admission** — ``ServeAdmission``'s queue-cap tail-drop and
  watermark stalls are carry arithmetic: shed requests are marked (with
  their round) in-carry, and a slot stalls when its lane's
  published-undelivered+backlog inflight (read off the previous round's
  in-carry watermarks, exactly what the host loop reads off
  ``StreamView``) exceeds ``stall_backlog``.
* **stall schedules** — a precomputed boolean ``(rounds, G, B)`` mask
  is an operand; scheduled slots decode null steps with no host hop.
  Only callable ``stall_fn``\\ s fall back.
* **view changes (``fail_at``)** — the loop is WEDGE-CAPABLE: it exits
  at the failure round, the host performs the PR 5/PR 7 cut (ragged
  trim, slot compaction, re-pinned holds, head-of-queue re-admission —
  the same :meth:`ReplicatedEngine._fail_nodes`), and a NEW fused
  program runs the next epoch with the ``EpochCarry`` resend as its
  initial backlog.  A serve run with one cut is two device programs,
  not hundreds of host rounds; ``host_hops`` stays 0 between cuts.

Equivalence contract (tested bit-for-bit in tests/test_serve_fused.py):
the same masked decode body runs in both paths; the multicast rounds ARE
:func:`repro.core.sweep.step_backlog` on the same ``ready`` counts,
handed to :meth:`repro.core.group.GroupStream.absorb` so report and
delivery logs come from the identical post-processing; holds pin and
release against the in-carry watermark with the same arithmetic as
:meth:`ReplicatedEngine._sync_holds` / ``app_publish_index``; and the
cut itself is the SAME host code both paths run.

What still falls back to the per-round loop: arbitrary ``arrive_fn`` /
``stall_fn`` host callbacks, ``settle_max`` (the capped host drain),
heterogeneous replicas, and ``fail_at`` cuts that leave replicas with
unequal slot or subscriber counts (the stacked program needs one
homogeneous shape per epoch).  The reason is recorded in
``extras["serve"]["fused_fallback"]``.
"""

from __future__ import annotations

import math
import time
from collections import deque
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import group as group_mod
from repro.core import sweep as sweep_mod
from repro.core.group import TRACE_EVENTS, RunReport, fused_stream_program
from repro.models import masking
from repro.models.layers import ParamSpec
from repro.serve.engine import DECODE_COMPILER_OPTIONS


class FusedUnsupported(Exception):
    """The workload needs a feature only the per-round loop has; the
    caller falls back (explicitly, in extras) rather than fail."""


def fused_fallback_reason(rep, *, fail_at=None, arrive_fn=None,
                          arrive_schedule=None, admission=None,
                          settle_max=None) -> Optional[str]:
    """Why this run cannot take the fused path (None = it can).

    ``fail_at`` must already be wave-normalized ({round: [[nodes]]}).
    The fused program handles precomputed arrival schedules, ndarray
    stall masks, ``ServeAdmission`` policies and homogeneous ``fail_at``
    cuts in-graph; only true host callbacks, capped settles, and
    heterogeneity keep the per-round path."""
    if arrive_fn is not None:
        return "arrive_fn: arbitrary open-loop arrivals are host callbacks"
    if rep.stall_fn is not None and not isinstance(rep.stall_fn,
                                                  np.ndarray):
        return "stall_fn: arbitrary client stalls are host callbacks"
    if isinstance(rep.stall_fn, np.ndarray):
        m = rep.stall_fn
        if m.ndim != 3 or m.shape[1] != len(rep.engines) \
                or m.shape[2] != rep.engines[0].ecfg.max_batch:
            return ("stall_fn mask must be (rounds, G, slots) boolean, "
                    f"got {m.shape}")
    if settle_max is not None:
        return "settle_max: capped settle needs the host drain loop"
    e0 = rep.engines[0]
    for eng in rep.engines:
        if (eng.cfg is not e0.cfg and eng.cfg != e0.cfg) \
                or eng.ecfg.max_batch != e0.ecfg.max_batch \
                or eng.ecfg.max_len != e0.ecfg.max_len \
                or eng.ecfg.eos_id != e0.ecfg.eos_id:
            return "heterogeneous replicas (mixed model/engine configs)"
        if eng.params is not e0.params:
            return ("replicas do not share one params tree (the fused "
                    "program folds every replica's slots into one "
                    "decode batch)")
        if any(r is not None for r in eng.slot_req):
            return "engines must start with empty slot rings"
    if fail_at:
        # Every cut must leave the replicas homogeneous — equal live
        # slot counts AND equal live subscriber counts — or the stacked
        # one-shape-per-epoch program cannot express the next epoch.
        sub_to_g = {n: g for g, t in enumerate(rep.topics)
                    for n in t.subscribers}
        dead_slots = [set() for _ in rep.engines]
        dead_subs = [set() for _ in rep.engines]
        for rnd in sorted(fail_at):
            for wave in fail_at[rnd]:
                for n in wave:
                    if n in rep._node_to_slot:
                        g, s = rep._node_to_slot[n]
                        dead_slots[g].add(s)
                    elif n in sub_to_g:
                        dead_subs[sub_to_g[n]].add(n)
                    else:
                        return (f"fail_at names node {n}, which is "
                                "neither a slot node nor a subscriber")
            if len({len(d) for d in dead_slots}) > 1 \
                    or len({len(d) for d in dead_subs}) > 1:
                return ("fail_at cut at round %d leaves heterogeneous "
                        "replicas (unequal live slot/subscriber "
                        "counts); the fused stack needs one shape per "
                        "epoch" % rnd)
    sched_reqs = [q for row in (arrive_schedule or [])
                  for cell in row for q in (cell or ())]
    all_reqs = [q for eng in rep.engines for q in eng.queue] + sched_reqs
    if not all_reqs:
        return "empty workload"
    if any(len(q.prompt) == 0 for q in all_reqs):
        return "empty prompts"
    if any(len(q.prompt) > e0.ecfg.max_len - 2
           or len(q.prompt) + q.max_new_tokens > e0.ecfg.max_len
           for q in all_reqs):
        return "request would overflow max_len mid-run"
    return None


# ---------------------------------------------------------------------------
# The one-program-per-epoch serve run
# ---------------------------------------------------------------------------

def _round_budget(n_reqs: int, slots: int, max_new: int, window: int,
                  n_members: int, max_rounds: int, *,
                  arrive_rounds: int = 0,
                  stall_slack: int = 0) -> Tuple[int, int]:
    """(serve-round cap, total cap incl. settle) — generous analytic
    bounds; a run that overflows them falls back to the unfused loop
    rather than truncate.  Open-loop runs add the arrival horizon and
    scheduled-stall slack (each stalled slot-round delays at most one
    decode round); backlog stalls self-resolve within the window
    throttle already covered per wave, doubled for slack."""
    waves = max(1, math.ceil(n_reqs / max(slots, 1)))
    per_wave = max_new + 8 + 3 * math.ceil((max_new + 1)
                                           / max(window, 1))
    serve = min(max_rounds,
                2 * waves * per_wave + arrive_rounds + stall_slack + 32)
    settle = 2 * n_members + 16 + 3 * math.ceil(
        slots * (max_new + 2) / max(window, 1))
    return serve, serve + settle


def _fold_caches(specs, trees):
    """Concatenate per-replica cache trees along each leaf's batch axis:
    the fused program decodes ALL replicas' slots in ONE masked step
    (batch = G * slots).  Every decode-body op is row-independent along
    the batch axis, so slot (g, s)'s arithmetic — and therefore its
    tokens and state — is bit-identical to the per-replica step."""
    return jax.tree.map(
        lambda sp, *xs: jnp.concatenate(
            xs, axis=masking.batch_axis(sp)),
        specs, *trees, is_leaf=lambda x: isinstance(x, ParamSpec))


def _unfold_caches(specs, tree, n_g, slots):
    """Split a folded cache tree back into per-replica trees."""
    def cut(g):
        return jax.tree.map(
            lambda sp, x: jax.lax.slice_in_dim(
                x, g * slots, (g + 1) * slots,
                axis=masking.batch_axis(sp)),
            specs, tree, is_leaf=lambda x: isinstance(x, ParamSpec))
    return [cut(g) for g in range(n_g)]


def _build_program(decode_body, reset_body, specs, shapes, rank_slot):
    """Trace-once builder for one epoch shape (see
    :func:`repro.core.group.fused_stream_program`).

    ``rank_slot[g]`` maps the epoch's live sender ranks to engine slots
    (identity before any cut; compacted survivors after one) — baked in
    as a static constant, like the shape tuple.  Everything dynamic —
    the arrival matrix, stall mask, admission scalars, requeue list,
    resend backlog, and the epoch's initial engine/queue state — is a
    traced operand, so a repeat run (and every same-shape epoch) reuses
    the compiled program."""
    (n_g, B, S, N, window, backend, R, P, t_serve_cap, t_total,
     eos_id, max_len, T_arr, T_stall, V) = shapes
    win_arr = np.full(n_g, window, np.int32)
    ring = window if backend == "pallas" else 0
    receive_fn = group_mod._kernel_receive(ring) \
        if backend == "pallas" else None
    i32 = jnp.int32
    rank_slot_c = jnp.asarray(rank_slot, jnp.int32)        # (G, S)
    slot_rank = np.zeros((n_g, B), np.int64)
    live_np = np.zeros((n_g, B), bool)
    for g in range(n_g):
        for r, s in enumerate(rank_slot[g]):
            slot_rank[g, s] = r
            live_np[g, s] = True
    slot_rank_c = jnp.asarray(slot_rank, jnp.int32)        # (G, B)
    live_c = jnp.asarray(live_np)                          # (G, B)
    ranks = jnp.arange(S)
    ridx_r = jnp.arange(R)

    def take_rank(x):
        """(G, S) rank-space array -> (G, B) per-slot view (dead slots
        read garbage lane 0 — every consumer masks them out)."""
        return jnp.take_along_axis(x, slot_rank_c, axis=1)

    def program(params, caches, ops):
        TRACE_EVENTS.append(((n_g, N, S), (window,) * n_g,
                             backend + "+decode"))
        t0, t_stop = ops["t0"], ops["t_stop"]
        arrive_rounds = ops["arrive_rounds"]

        def queue_len(c):
            elig = (ridx_r[None, :] < c["avail"][:, None]) \
                & ~c["admitted"] & ~c["shed"]
            pend = ops["n_rq"] - c["rq_head"]
            return pend + jnp.sum(elig.astype(i32), axis=1)

        def serving_now(c):
            live = jnp.any(c["active"]) | jnp.any(queue_len(c) > 0) \
                | (t0 + c["t_serve"] < arrive_rounds)
            return live & (c["t_serve"] < t_serve_cap)

        def body_fn(c):
            serving = serving_now(c)
            t = c["t"]
            t_g = t0 + t

            # ---- open-loop arrivals: the schedule row becomes this
            # round's arrival frontier advance (inert past the horizon
            # and during settle, where the row is zero by construction)
            arr = jnp.where(
                t_g < T_arr,
                jax.lax.dynamic_index_in_dim(
                    ops["arr_counts"],
                    jnp.clip(t_g, 0, max(T_arr, 1) - 1),
                    axis=0, keepdims=False),
                0).astype(i32)                              # (G,)
            avail = c["avail"] + arr
            elig = (ridx_r[None, :] < avail[:, None]) \
                & ~c["admitted"] & ~c["shed"]               # (G, R)
            n_rq_pend = ops["n_rq"] - c["rq_head"]          # (G,)
            qlen = n_rq_pend + jnp.sum(elig.astype(i32), axis=1)

            # ---- admission queue-cap: shed the tail (newest first) —
            # the host loop's `while len(queue) > cap: queue.pop()`.
            # Requeued head-of-queue entries are never shed here: the
            # cut already sheds instead of requeueing at cap, so the
            # overflow never exceeds the eligible-regular count.
            over = jnp.clip(qlen - ops["queue_cap"], 0, None)
            rev = jnp.cumsum(elig[:, ::-1].astype(i32),
                             axis=1)[:, ::-1]               # tail rank 1..
            shed_new = elig & (rev <= over[:, None])
            shed = c["shed"] | shed_new
            shed_round = jnp.where(shed_new, t_g, c["shed_round"])
            elig = elig & ~shed_new
            qlen = qlen - jnp.sum(shed_new.astype(i32), axis=1)
            depth = jnp.sum(qlen).astype(i32)

            # ---- stall mask for this round: the precomputed schedule
            # row plus the watermark stall — a lane whose
            # published-undelivered + backlog inflight (previous round's
            # carry watermarks, what the host reads off StreamView)
            # exceeds stall_backlog decodes a null step.  t > 0 gates
            # the watermark read exactly like the host loop's
            # `_last_view is None` on the first round of an epoch.
            sched_stall = jnp.where(
                t_g < T_stall,
                jax.lax.dynamic_index_in_dim(
                    ops["stall_mask"],
                    jnp.clip(t_g, 0, max(T_stall, 1) - 1),
                    axis=0, keepdims=False),
                False)                                      # (G, B)
            d_prev = jnp.min(c["states"].delivered_num, axis=1)  # (G,)
            sd_prev = jnp.where(
                d_prev[:, None] >= ranks[None, :],
                (d_prev[:, None] - ranks[None, :]) // S + 1, 0)
            inflight = (c["states"].published - sd_prev
                        + c["backlogs"])                    # (G, S)
            wm_stall = (take_rank(inflight) > ops["stall_backlog"]) \
                & (t > 0)

            # ---- engine phase (admission -> prefill -> decode ->
            # finish), skipped entirely on settle rounds --------------
            fields = (c["caches"], c["active"], c["held"],
                      c["hold_target"], c["hold_idx"], c["pos"],
                      c["last_tok"], c["slot_rid"], c["emitted"],
                      c["slot_max_new"], c["apps_enq"], c["admitted"],
                      c["rq_head"], c["stall_ct"])

            def engine_phase(f):
                (caches, active, held, target, hidx, pos, last, rid,
                 emitted, mnew, enq, admitted, rq_head, stall_ct) = f
                # admission: the k-th free live slot (slot order) takes
                # the k-th queued request — requeued head-of-queue
                # entries first, then eligible regulars in arrival
                # order (ServeEngine._admit's popleft loop)
                free = (~active) & (~held) & live_c
                order = jnp.cumsum(free.astype(i32), axis=1) - 1
                admit = free & (order < qlen[:, None])
                from_rq = admit & (order < n_rq_pend[:, None])
                rq_idx = jnp.clip(rq_head[:, None] + order, 0, V - 1)
                rq_ridx = jnp.take_along_axis(ops["requeue"], rq_idx,
                                              axis=1)
                j = order - n_rq_pend[:, None]              # (G, B)
                erank = jnp.cumsum(elig.astype(i32), axis=1) - 1
                sel = (elig[:, None, :]
                       & (erank[:, None, :] == j[:, :, None])
                       & admit[:, :, None]
                       & ~from_rq[:, :, None])              # (G, B, R)
                reg_ridx = jnp.sum(
                    sel * ridx_r[None, None, :], axis=2).astype(i32)
                ridx = jnp.where(from_rq, rq_ridx, reg_ridx)
                safe_r = jnp.where(admit, ridx, 0)
                admitted = admitted | jnp.any(sel, axis=1)
                rq_head = rq_head + jnp.sum(from_rq.astype(i32),
                                            axis=1)
                plen = jnp.take_along_axis(ops["prompt_len"], safe_r,
                                           axis=1)
                amnew = jnp.take_along_axis(ops["max_new"], safe_r,
                                            axis=1)
                pslot = jnp.stack(
                    [jnp.take(ops["prompts"][g], safe_r[g], axis=0)
                     for g in range(n_g)])                  # (G, B, P)
                rid = jnp.where(admit, ridx, rid)
                mnew = jnp.where(admit, amnew, mnew)
                emitted = jnp.where(admit, 0, emitted)

                # prefill: every admitted slot — across ALL replicas,
                # the caches are folded into one (G*B)-row batch —
                # feeds prompt token p at position p; bystanders are
                # masked no-ops.  Rows are independent, so this equals
                # the sequential per-slot prefill of the unfused engine
                # bit-for-bit — including the admission reset.
                def prefill(cs):
                    cs = reset_body(cs, admit.reshape(-1))

                    def pf(p, cs):
                        valid = admit & (p < plen)          # (G, B)
                        tok = jax.lax.dynamic_index_in_dim(
                            pslot, p, axis=2, keepdims=False)
                        tokens = jnp.where(valid, tok, 0).reshape(-1, 1)
                        posv = jnp.where(admit, p, pos).reshape(-1)
                        _, nc = decode_body(params, cs,
                                            tokens.astype(i32),
                                            posv.astype(i32),
                                            valid.reshape(-1))
                        return nc

                    return jax.lax.fori_loop(0, P, pf, cs)

                caches = jax.lax.cond(jnp.any(admit), prefill,
                                      lambda cs: cs, caches)
                pos = jnp.where(admit, plen, pos)
                # first decode input after prefill is the LAST prompt
                # token (fed once more at position P)
                lastp = jnp.take_along_axis(
                    pslot, jnp.maximum(plen - 1, 0)[:, :, None],
                    axis=2)[:, :, 0]
                last = jnp.where(admit, lastp, last)
                active = active | admit

                # stalls bind AFTER admission (a stalled slot still
                # admits and prefills — ServeEngine.step's ordering);
                # stalled occupied slots count, then sit out the decode
                stall_now = (sched_stall | wm_stall) & active
                stall_ct = stall_ct + jnp.sum(
                    stall_now.astype(i32))
                emit = active & ~stall_now

                # main decode: one masked step for every replica's
                # whole ring at once (the folded batch)
                tokens = jnp.where(emit, last, 0).reshape(-1, 1)
                logits, caches = decode_body(params, caches,
                                             tokens.astype(i32),
                                             pos.reshape(-1).astype(i32),
                                             emit.reshape(-1))
                flat = logits.astype(jnp.float32).reshape(n_g * B, -1)
                nxt = jnp.argmax(flat, axis=-1).astype(i32) \
                    .reshape(n_g, B)
                last = jnp.where(emit, nxt, last)
                emitted = emitted + emit.astype(i32)
                pos = pos + emit.astype(i32)
                done = emitted >= mnew
                if eos_id is not None:
                    done = done | (nxt == eos_id)
                fin = emit & (done | (pos >= max_len - 1))
                active = active & ~fin
                pos = jnp.where(fin, 0, pos)

                counts = admit.astype(i32) + emit.astype(i32)
                enq = enq + counts
                # finished slots hold until the delivery watermark
                # passes their last enqueued app (the SMC slot-reuse
                # rule)
                held = held | fin
                target = jnp.where(fin, enq, target)
                hidx = jnp.where(fin, -1, hidx)
                adm_rec = jnp.where(admit, ridx, -1)
                tok_rec = jnp.where(emit, nxt, -1)
                return ((caches, active, held, target, hidx, pos, last,
                         rid, emitted, mnew, enq, admitted, rq_head,
                         stall_ct),
                        (counts, adm_rec, tok_rec, fin))

            def idle_phase(f):
                z = jnp.zeros((n_g, B), i32)
                neg = jnp.full((n_g, B), -1, i32)
                return f, (z, neg, neg, jnp.zeros((n_g, B), bool))

            fields, (counts, adm_rec, tok_rec, fin) = jax.lax.cond(
                serving, engine_phase, idle_phase, fields)
            (caches, active, held, target, hidx, pos, last, rid,
             emitted, mnew, enq, admitted, rq_head, stall_ct) = fields

            # ---- multicast sweep: the SAME round body the stream
            # runs, on the live sender ranks (compacted slot order) ---
            counts_rank = jnp.take_along_axis(counts, rank_slot_c,
                                              axis=1)     # (G, S)
            old = c["states"]
            (states, backlogs), (batch, pub, nulls) = \
                sweep_mod.stream_stacked(
                    old, c["backlogs"], counts_rank, windows=win_arr,
                    null_send=True, receive_fn=receive_fn)

            # ---- holds: pin at the k-th app's publish index, release
            # on the watermark (ReplicatedEngine._sync_holds, in-graph,
            # gathered from rank space into slot space) ---------------
            app_sent_s = take_rank(states.app_sent)
            crossed = held & (hidx < 0) & (target > 0) \
                & (app_sent_s >= target)
            pin = take_rank(old.published) \
                + (target - take_rank(old.app_sent)) - 1
            hidx = jnp.where(crossed, pin, hidx)
            d = jnp.min(states.delivered_num, axis=1)       # (G,)
            sd = jnp.where(d[:, None] >= ranks[None, :],
                           (d[:, None] - ranks[None, :]) // S + 1, 0)
            freed = held & (hidx >= 0) & (take_rank(sd) > hidx)
            held = held & ~freed

            return {
                "t": t + 1,
                "t_serve": c["t_serve"] + serving.astype(i32),
                "states": states, "backlogs": backlogs,
                "caches": caches, "active": active, "held": held,
                "hold_target": target, "hold_idx": hidx, "pos": pos,
                "last_tok": last, "slot_rid": rid, "emitted": emitted,
                "slot_max_new": mnew, "apps_enq": enq,
                "avail": avail, "admitted": admitted, "shed": shed,
                "shed_round": shed_round, "rq_head": rq_head,
                "stall_ct": stall_ct,
                "tb_batch": c["tb_batch"].at[t].set(batch.astype(i32)),
                "tb_pub": c["tb_pub"].at[t].set(pub.astype(i32)),
                "tb_nulls": c["tb_nulls"].at[t].set(nulls.astype(i32)),
                "tb_admit": c["tb_admit"].at[t].set(adm_rec),
                "tb_tok": c["tb_tok"].at[t].set(tok_rec),
                "tb_fin": c["tb_fin"].at[t].set(fin),
                "tb_free": c["tb_free"].at[t].set(freed),
                "tb_backlog": c["tb_backlog"].at[t].set(
                    jnp.sum(backlogs).astype(i32)),
                "tb_depth": c["tb_depth"].at[t].set(depth),
            }

        init = ops["init"]
        c = {
            "t": jnp.asarray(0, i32), "t_serve": jnp.asarray(0, i32),
            "states": sweep_mod.batch_states(N, S, n_g),
            "backlogs": ops["backlogs0"].astype(i32),
            "caches": _fold_caches(specs, caches),
            "active": init["active"], "held": init["held"],
            "hold_target": init["hold_target"].astype(i32),
            "hold_idx": jnp.full((n_g, B), -1, i32),
            "pos": init["pos"].astype(i32),
            "last_tok": init["last_tok"].astype(i32),
            "slot_rid": init["slot_rid"].astype(i32),
            "emitted": init["emitted"].astype(i32),
            "slot_max_new": init["slot_max_new"].astype(i32),
            "apps_enq": init["apps_enq"].astype(i32),
            "avail": init["avail"].astype(i32),
            "admitted": init["admitted"], "shed": init["shed"],
            "shed_round": init["shed_round"].astype(i32),
            "rq_head": jnp.zeros((n_g,), i32),
            "stall_ct": jnp.asarray(0, i32),
            "tb_batch": jnp.zeros((t_total, n_g, N), i32),
            "tb_pub": jnp.zeros((t_total, n_g, S), i32),
            "tb_nulls": jnp.zeros((t_total, n_g, S), i32),
            "tb_admit": jnp.full((t_total, n_g, B), -1, i32),
            "tb_tok": jnp.full((t_total, n_g, B), -1, i32),
            "tb_fin": jnp.zeros((t_total, n_g, B), bool),
            "tb_free": jnp.zeros((t_total, n_g, B), bool),
            "tb_backlog": jnp.zeros((t_total,), i32),
            "tb_depth": jnp.zeros((t_total,), i32),
        }

        def cond(c):
            q = sweep_mod.quiescent_stacked(c["states"], c["backlogs"])
            return (c["t"] < t_total) & (c["t_serve"] < t_stop) \
                & (serving_now(c) | ~q)

        out = jax.lax.while_loop(cond, body_fn, c)
        # hand per-replica cache trees back (sliced in-program: free
        # at trace time, no eager per-leaf dispatches on the host)
        out["caches"] = tuple(
            _unfold_caches(specs, out["caches"], n_g, B))
        return out

    return jax.jit(program, compiler_options=DECODE_COMPILER_OPTIONS)


def _owner_fill(tb_admit: np.ndarray, init_rid: np.ndarray) -> np.ndarray:
    """Per-(round, replica, slot) owning request index: one vectorized
    forward-fill of the last admission at or before each round over the
    whole ``tb_admit`` buffer (replaces the per-(round, slot) O(T)
    column scans of the original reconstruction).  Rounds before a
    slot's first in-epoch admission fall back to ``init_rid`` — the
    request occupying the slot when the epoch began (-1 if idle)."""
    t_n = tb_admit.shape[0]
    if t_n == 0:
        return np.zeros_like(tb_admit)
    idx = np.where(tb_admit >= 0, np.arange(t_n)[:, None, None], -1)
    last = np.maximum.accumulate(idx, axis=0)
    own = np.take_along_axis(tb_admit, np.maximum(last, 0), axis=0)
    return np.where(last >= 0, own, init_rid[None].astype(tb_admit.dtype))


def run_fused(rep, *, max_rounds: int = 10_000, fail_at=None,
              arrive_schedule=None, arrive_rounds: int = 0,
              admission=None) -> Optional[RunReport]:
    """Execute one serve run of ``rep`` (a
    :class:`repro.serve.fanout.ReplicatedEngine`) as one compiled device
    program per membership epoch, then reconstruct the engines' and
    fan-out's host state from the device round traces so callers see
    exactly what the per-round loop would have produced.

    ``fail_at`` must be wave-normalized.  With cuts scheduled, the
    while_loop exits at each failure round, the host performs the PR 5 /
    PR 7 cut through the SAME :meth:`ReplicatedEngine._fail_nodes` the
    unfused loop uses, and the next epoch re-enters a fused program with
    the ``EpochCarry`` resend as its initial backlog.

    Returns None when the FIRST epoch overflows the analytic round
    budget (the caller falls back to the unfused loop — engine state is
    untouched until the first reconstruction, so the fallback restarts
    cleanly).  A later epoch overflowing raises RuntimeError: the run is
    already partially applied and cannot be replayed host-side.  Raises
    :class:`FusedUnsupported` for unsupported workload shapes."""
    from repro.serve.fanout import _SlotHold

    engines = rep.engines
    e0 = engines[0]
    n_g, B = len(engines), e0.ecfg.max_batch
    subs = len(rep.topics[0].subscribers)
    fail_at = dict(fail_at or {})

    # ---- assemble the request universe: initial queues + the truncated
    # arrival schedule, in arrival order (index order == FIFO order) ---
    n_init = [len(eng.queue) for eng in engines]
    reqs = [list(eng.queue) for eng in engines]
    sched = list(arrive_schedule or [])
    if sched and arrive_rounds <= 0:
        arrive_rounds = len(sched)
    t_arr = min(len(sched), arrive_rounds) if sched else 0
    arr_counts = np.zeros((max(t_arr, 1), n_g), np.int32)
    arrive_at: List[Tuple[int, int]] = []    # (rid, round submitted)
    for rnd in range(t_arr):
        row = sched[rnd]
        for g in range(n_g):
            cell = list(row[g]) if row[g] else []
            arr_counts[rnd, g] = len(cell)
            for q in cell:
                reqs[g].append(q)
                arrive_at.append((q.rid, rnd))
    r_max = max(len(r) for r in reqs)
    if r_max == 0:
        raise FusedUnsupported("empty workload")
    p_max = max(len(q.prompt) for r in reqs for q in r)
    m_max = max(q.max_new_tokens for r in reqs for q in r)
    rid_to_idx = [{q.rid: i for i, q in enumerate(reqs[g])}
                  for g in range(n_g)]

    stalls = rep.stall_fn if isinstance(rep.stall_fn, np.ndarray) \
        else None
    t_stall = int(stalls.shape[0]) if stalls is not None else 0
    stall_mask = np.zeros((max(t_stall, 1), n_g, B), bool)
    if stalls is not None:
        stall_mask[:t_stall] = stalls.astype(bool)

    big = np.int32(2 ** 30)
    q_cap = big if admission is None or admission.queue_cap is None \
        else np.int32(admission.queue_cap)
    s_backlog = big if admission is None \
        or admission.stall_backlog is None \
        else np.int32(admission.stall_backlog)

    rep._reset_run_state()
    window = rep.topics[0].window
    t_serve_cap, t_total = _round_budget(
        r_max, B, m_max, window, B + subs, max_rounds,
        arrive_rounds=arrive_rounds, stall_slack=int(stall_mask.sum()))
    wall0 = time.perf_counter()
    now = time.time()
    tok0 = sum(len(r.tokens_out) for eng in engines
               for r in eng.completed)
    req0 = sum(len(eng.completed) for eng in engines)
    steps0 = sum(e.decode_steps for e in engines)

    # ---- host-side run accumulators ----------------------------------
    depth_all: List[int] = []
    backlog_all: List[int] = []
    birth = np.full((n_g, B), -1, np.int64)   # current hold's fin round
    prev_shed = np.zeros((n_g, r_max), bool)
    stall_total = 0
    fused_rounds = 0
    epochs_run = 0

    # epoch-1 initial state: everything idle, identity rank map
    init = {
        "active": np.zeros((n_g, B), bool),
        "held": np.zeros((n_g, B), bool),
        "hold_target": np.zeros((n_g, B), np.int32),
        "pos": np.zeros((n_g, B), np.int32),
        "last_tok": np.zeros((n_g, B), np.int32),
        "slot_rid": np.full((n_g, B), -1, np.int32),
        "emitted": np.zeros((n_g, B), np.int32),
        "slot_max_new": np.zeros((n_g, B), np.int32),
        "apps_enq": np.zeros((n_g, B), np.int32),
        "avail": np.asarray(n_init, np.int32),
        "admitted": np.zeros((n_g, r_max), bool),
        "shed": np.zeros((n_g, r_max), bool),
        "shed_round": np.full((n_g, r_max), -1, np.int32),
    }
    requeue = np.full((n_g, 1), -1, np.int32)
    n_rq = np.zeros(n_g, np.int32)
    caches_dev: Tuple = tuple(eng.cache for eng in engines)
    backlogs0 = np.zeros((n_g, B), np.int32)
    bound = None
    t0 = 0
    pending = deque(sorted(fail_at))

    while True:
        rank_slot = [list(r) for r in rep._rank_slot]
        s_live = len(rank_slot[0])
        if any(len(r) != s_live for r in rank_slot):
            raise FusedUnsupported(
                "cut left replicas with unequal live slot counts")
        if bound is None:
            n_live = B + subs
        else:
            if bound.stream._mask_args:
                raise RuntimeError(
                    "fused epoch after a cut has heterogeneous topic "
                    "shapes; the fallback precheck should have caught "
                    "this")
            n_live = bound.stream.n_members[0]
            s_chk = bound.stream.n_senders[0]
            if s_chk != s_live:
                raise RuntimeError(
                    f"stream sender count {s_chk} disagrees with live "
                    f"slot count {s_live} after the cut")
        nxt_fail = pending[0] if pending else None
        t_stop = (nxt_fail - t0 + 1) if nxt_fail is not None else t_total
        v_cap = max(1, requeue.shape[1])

        shapes = (n_g, B, s_live, n_live, window, rep.backend, r_max,
                  p_max, t_serve_cap, t_total, e0.ecfg.eos_id,
                  e0.ecfg.max_len, t_arr, t_stall, v_cap)
        key = ("serve-fused", repr(e0.cfg), repr(e0.rt), shapes,
               tuple(tuple(r) for r in rank_slot))
        program = fused_stream_program(
            key, lambda: _build_program(e0._decode_body, e0._reset_body,
                                        e0.cache_specs, shapes,
                                        rank_slot))
        ops = {
            "prompts": _pad_prompts(reqs, n_g, r_max, p_max),
            "prompt_len": jnp.asarray(
                _req_field(reqs, n_g, r_max,
                           lambda q: len(q.prompt))),
            "max_new": jnp.asarray(
                _req_field(reqs, n_g, r_max,
                           lambda q: q.max_new_tokens)),
            "arr_counts": jnp.asarray(arr_counts),
            "stall_mask": jnp.asarray(stall_mask),
            "requeue": jnp.asarray(requeue),
            "n_rq": jnp.asarray(n_rq),
            "queue_cap": jnp.asarray(q_cap, jnp.int32),
            "stall_backlog": jnp.asarray(s_backlog, jnp.int32),
            "arrive_rounds": jnp.asarray(arrive_rounds, jnp.int32),
            "t0": jnp.asarray(t0, jnp.int32),
            "t_stop": jnp.asarray(t_stop, jnp.int32),
            "backlogs0": jnp.asarray(backlogs0[:, :s_live]),
            "init": {k: jnp.asarray(v) for k, v in init.items()},
        }
        out = program(e0.params, caches_dev, ops)
        epochs_run += 1

        if bound is None:
            # bind the stream while the device loop runs (dispatch is
            # async; the stream is first needed at absorb time)
            bound = rep.domain.bind(backend=rep.backend)
            stream = bound.stream
            if stream._mask_args:
                raise FusedUnsupported(
                    "heterogeneous topic shapes (padded stack) — fused "
                    "path needs a homogeneous slot ring")
            if not stream.group.cfg.flags.null_send:
                raise FusedUnsupported(
                    "null_send disabled: the in-graph drain may never "
                    "quiesce")
            if stream.windows[0] != window:
                raise FusedUnsupported(
                    "topic window disagrees with the bound stream's "
                    "protocol window")

        host = jax.device_get({k: out[k] for k in (
            "t", "t_serve", "active", "held", "hold_target", "pos",
            "last_tok", "slot_rid", "emitted", "slot_max_new",
            "apps_enq", "avail", "admitted", "shed", "shed_round",
            "rq_head", "stall_ct", "tb_batch", "tb_pub", "tb_nulls",
            "tb_admit", "tb_tok", "tb_fin", "tb_free", "tb_backlog",
            "tb_depth")})
        t_end = int(host["t"])
        t_serve = int(host["t_serve"])
        wedged = nxt_fail is not None and t_serve >= t_stop
        if not wedged:
            qleft = int(n_rq.sum()) - int(host["rq_head"].sum()) + int(
                ((np.arange(r_max)[None, :] < host["avail"][:, None])
                 & ~host["admitted"] & ~host["shed"]).sum())
            live = host["active"].any() or qleft > 0
            overflow = (live and t_serve < max_rounds) or (
                t_end >= t_total and not bool(
                    sweep_mod.quiescent_stacked(out["states"],
                                                out["backlogs"])))
            if overflow:
                if epochs_run == 1:
                    return None      # budget overflow: fall back clean
                raise RuntimeError(
                    "fused epoch %d overflowed its round budget "
                    "mid-run (t=%d, budget=%d); raise max_rounds or "
                    "run unfused" % (epochs_run, t_end, t_total))

        # ---- per-epoch host reconstruction (one crossing per epoch) --
        stall_total += int(host["stall_ct"])
        fused_rounds += t_end
        birth = _apply_epoch(
            rep, reqs, host, out, t0, t_serve, t_end, rank_slot,
            bound.stream, now, birth, prev_shed, depth_all, backlog_all)
        prev_shed = host["shed"].copy()
        _materialize_engines(rep, reqs, host, requeue, n_rq, _SlotHold,
                             birth)
        for g, eng in enumerate(engines):
            eng.cache = out["caches"][g]
        caches_dev = tuple(out["caches"])

        if not wedged:
            break

        # ---- the wedge: host performs the PR 5/PR 7 cut, the next
        # epoch re-enters a fused program with the resend as backlog ---
        pending.popleft()
        bound = rep._fail_nodes(bound, fail_at[nxt_fail], nxt_fail,
                                admission)
        t0 = nxt_fail + 1
        init = _epoch_init(rep, reqs, rid_to_idx, host, n_g, B, r_max)
        requeue, n_rq = _requeue_ops(rep, rid_to_idx,
                                     host["admitted"], n_g)
        backlogs0 = np.asarray(bound.stream._backlogs, np.int32)
        birth = _hold_births(rep, birth, n_g, B)

    # ---- finish: settle already ran in-graph; post-process ----------
    total_serve = t0 + t_serve
    unreached = sorted(r for r in fail_at if r >= total_serve)
    report, logs = bound.finish()
    rep.queue_depth_log = list(depth_all)
    rep.backlog_log = list(backlog_all)
    rep.stall_rounds = stall_total
    wall = time.perf_counter() - wall0
    tokens = sum(len(r.tokens_out) for eng in engines
                 for r in eng.completed) - tok0
    report.extras["delivery_logs"] = logs
    report.extras["serve"] = {
        "replicas": n_g,
        "engine_rounds": total_serve,
        "drained": all(eng.drained() for eng in engines),
        "decode_steps": sum(e.decode_steps
                            for e in engines) - steps0,
        "requests": sum(len(e.completed) for e in engines) - req0,
        "tokens": tokens,
        "tokens_per_s": tokens / wall if wall > 0 else 0.0,
        "stall_rounds": stall_total,
        "held_slots": sum(len(h) for h in rep._holds),
        "view_changes": len(rep.view_log),
        "slot_failures": len(rep.slot_failures),
        "voided_requests": sum(1 for r in rep.slot_failures
                               if r["voided_rid"] is not None),
        "requeued_requests": sum(1 for r in rep.slot_failures
                                 if r["requeued"]),
        "slot_failure_log": list(rep.slot_failures),
        "fail_at_unreached": unreached,
        "shed_requests": len(rep.shed_log),
        # per-RUN maxima over THIS run's logs (not whole-history state:
        # a fused run after a prior run must not report stale maxima)
        "max_queue_depth": max(depth_all, default=0),
        "max_backlog": max(backlog_all, default=0),
        "wall_s": wall,
        "fused": True,
        "host_hops": 0,
        "fused_rounds": fused_rounds,
        "fused_round_budget": t_total,
        "fused_epochs": epochs_run,
    }
    for rid, rnd in arrive_at:
        rep.submit_rounds[rid] = rnd
    rep.last_report = report
    return report


# ---------------------------------------------------------------------------
# Host-side epoch reconstruction
# ---------------------------------------------------------------------------

def _req_field(reqs, n_g, r_max, fn):
    arr = np.zeros((n_g, r_max), np.int32)
    for g, rs in enumerate(reqs):
        for i, q in enumerate(rs):
            arr[g, i] = fn(q)
    return arr


def _pad_prompts(reqs, n_g, r_max, p_max):
    prompts = np.zeros((n_g, r_max, p_max), np.int32)
    for g, rs in enumerate(reqs):
        for i, q in enumerate(rs):
            prompts[g, i, :len(q.prompt)] = np.asarray(q.prompt,
                                                       np.int32)
    return jnp.asarray(prompts)


def _apply_epoch(rep, reqs, host, out, t0, t_serve, t_end, rank_slot,
                 stream, now, birth, prev_shed, depth_all, backlog_all):
    """Replay one epoch's device traces into the fan-out's host state —
    tokens, completions, admission/finish/free/shed bookkeeping, the
    depth/backlog logs — and absorb the multicast rounds into the bound
    stream.  Returns the updated per-slot hold-birth rounds (used to
    keep free_rounds in the host loop's hold-insertion order)."""
    engines = rep.engines
    n_g = len(engines)
    tb = {k: host[k][:t_end] for k in
          ("tb_batch", "tb_pub", "tb_nulls", "tb_admit", "tb_tok",
           "tb_fin", "tb_free", "tb_backlog", "tb_depth")}
    counts = (tb["tb_admit"] >= 0).astype(np.int64) \
        + (tb["tb_tok"] >= 0).astype(np.int64)          # (T, G, B)
    stream.absorb(
        out["states"], out["backlogs"], list(tb["tb_batch"]),
        list(tb["tb_pub"]), list(tb["tb_nulls"]),
        [counts[:, g, :].sum(axis=0)[np.asarray(rank_slot[g])]
         for g in range(n_g)])

    init_rid = np.where(host["slot_rid"] >= 0, host["slot_rid"], -1)
    # the epoch-END slot_rid is not the start state; recover the start
    # owner by rolling admissions back: a slot's pre-epoch owner is only
    # needed for rounds BEFORE its first in-epoch admission, and that
    # owner is exactly the engine's slot_req at epoch entry — which
    # _materialize_engines wrote as reqs indices last epoch.  For the
    # first epoch every slot starts idle (-1).
    start_rid = np.full(init_rid.shape, -1, np.int64)
    for g, eng in enumerate(engines):
        for s, q in enumerate(eng.slot_req):
            if q is not None:
                start_rid[g, s] = _rid_index(reqs, g, q)
    own = _owner_fill(tb["tb_admit"], start_rid)

    # admissions: bookkeeping + prefill decode steps
    for t, g, s in zip(*np.nonzero(tb["tb_admit"] >= 0)):
        i = int(tb["tb_admit"][t, g, s])
        req = reqs[g][i]
        rep.admit_rounds[req.rid] = t0 + int(t)
        rep.admit_slots[req.rid] = (g, int(s))
        engines[g].decode_steps += len(req.prompt)
        req.tokens_out = []     # (re-)admission restarts from prompt

    # tokens, in round order (np.nonzero is t-major)
    for t, g, s in zip(*np.nonzero(tb["tb_tok"] >= 0)):
        reqs[g][own[t, g, s]].tokens_out.append(
            int(tb["tb_tok"][t, g, s]))

    # finishes: completion append order is (round, slot) per replica —
    # the per-round loop's order
    fins = sorted((int(t), int(g), int(s))
                  for t, g, s in zip(*np.nonzero(tb["tb_fin"])))
    for t, g, s in fins:
        req = reqs[g][own[t, g, s]]
        req.finished_at = now
        rep.finish_round_by_rid[req.rid] = t0 + t
        engines[g].completed.append(req)
        rep.finish_rounds.append((g, s, t0 + t))

    # sheds: round ascending, replica ascending, newest (highest
    # arrival index) first — the host loop's tail-pop order
    new_shed = host["shed"] & ~prev_shed
    evs = []
    for g in range(n_g):
        for i in np.nonzero(new_shed[g])[0]:
            evs.append((int(host["shed_round"][g, i]), g, -int(i)))
    for rnd, g, ni in sorted(evs):
        rep.shed_log.append((reqs[g][-ni].rid, rnd))

    # frees: serve-round frees at their round; settle-round frees all
    # land in the single post-finish sync at round t_serve, ordered by
    # hold creation (finish round, slot) per replica
    frees = []
    for t, g, s in zip(*np.nonzero(tb["tb_free"])):
        t, g, s = int(t), int(g), int(s)
        f_ts = [t0 + ft for (ft, gg, ss) in fins
                if gg == g and ss == s and ft <= t]
        b = max(f_ts) if f_ts else int(birth[g, s])
        frees.append((t0 + min(t, t_serve), g, b, s))
    for t, g, _b, s in sorted(frees):
        rep.free_rounds.append((g, s, t))

    # per-engine counters + run logs
    for g, eng in enumerate(engines):
        eng.rounds += t_serve
        eng.decode_steps += int(
            (tb["tb_tok"][:, g] >= 0).any(axis=1).sum())
        rep._apps_enqueued[g][:] = host["apps_enq"][g]
    depth_all.extend(int(x) for x in tb["tb_depth"][:t_serve])
    backlog_all.extend(int(x) for x in tb["tb_backlog"][:t_serve])

    # updated hold births: a held slot's current hold was created at
    # its last finish (this epoch, else carried from before)
    new_birth = birth.copy()
    for t, g, s in fins:
        new_birth[g, s] = t0 + t
    return new_birth


def _rid_index(reqs, g, q) -> int:
    for i, r in enumerate(reqs[g]):
        if r is q:
            return i
    raise KeyError(f"request rid={q.rid} not in replica {g}'s universe")


def _materialize_engines(rep, reqs, host, requeue, n_rq, slot_hold_cls,
                         birth):
    """Install the epoch-end carry as host engine/queue/hold state, so
    the cut (and the caller, after the final epoch) sees exactly what
    the per-round loop would have left behind."""
    n_g = len(rep.engines)
    r_max = host["admitted"].shape[1]
    for g, eng in enumerate(rep.engines):
        b = eng.ecfg.max_batch
        eng.slot_req = [None] * b
        eng.slot_len[:] = 0
        for s in range(b):
            if host["active"][g, s]:
                eng.slot_req[s] = reqs[g][int(host["slot_rid"][g, s])]
                eng.slot_len[s] = int(host["pos"][g, s])
        pend = [int(x) for x in
                requeue[g, int(host["rq_head"][g]):int(n_rq[g])]]
        elig = [i for i in range(int(host["avail"][g]))
                if not host["admitted"][g, i]
                and not host["shed"][g, i]]
        eng.queue = deque(reqs[g][i] for i in pend + elig)
        holds = {}
        order = sorted((int(birth[g, s]), s) for s in range(b)
                       if host["held"][g, s])
        for b_rnd, s in order:     # insertion order = creation order
            holds[s] = slot_hold_cls(
                target_apps=int(host["hold_target"][g, s]),
                last_idx=None, finished_round=max(b_rnd, 0))
        rep._holds[g] = holds


def _epoch_init(rep, reqs, rid_to_idx, host, n_g, b, r_max):
    """Build the next epoch's initial engine/queue carry from the
    post-cut host state (evictions, hold rebasing and re-queueing
    already applied by :meth:`ReplicatedEngine._fail_nodes`)."""
    init = {
        "active": np.zeros((n_g, b), bool),
        "held": np.zeros((n_g, b), bool),
        "hold_target": np.zeros((n_g, b), np.int32),
        "pos": np.zeros((n_g, b), np.int32),
        "last_tok": np.zeros((n_g, b), np.int32),
        "slot_rid": np.full((n_g, b), -1, np.int32),
        "emitted": np.zeros((n_g, b), np.int32),
        "slot_max_new": np.zeros((n_g, b), np.int32),
        "apps_enq": np.zeros((n_g, b), np.int32),
        "avail": host["avail"].astype(np.int32),
        "admitted": host["admitted"].copy(),
        "shed": host["shed"].copy(),
        "shed_round": host["shed_round"].astype(np.int32),
    }
    for g, eng in enumerate(rep.engines):
        for s in range(b):
            q = eng.slot_req[s]
            if q is not None:
                i = rid_to_idx[g][q.rid]
                init["active"][g, s] = True
                init["slot_rid"][g, s] = i
                init["pos"][g, s] = int(eng.slot_len[s])
                init["last_tok"][g, s] = (
                    q.tokens_out[-1] if q.tokens_out
                    else int(q.prompt[-1]))
                init["emitted"][g, s] = len(q.tokens_out)
                init["slot_max_new"][g, s] = q.max_new_tokens
        for s, hold in rep._holds[g].items():
            init["held"][g, s] = True
            init["hold_target"][g, s] = hold.target_apps
        init["apps_enq"][g, :] = rep._apps_enqueued[g]
    return init


def _requeue_ops(rep, rid_to_idx, admitted, n_g):
    """The post-cut head-of-queue re-admission list per replica: the
    queue's leading already-ADMITTED entries (a voided request the cut
    pushed back via ``appendleft``), which must admit before any
    eligible regular — regulars are never marked admitted while still
    queued, so the admitted flag is exactly the requeue marker."""
    rq: List[List[int]] = []
    for g, eng in enumerate(rep.engines):
        lst = []
        for q in eng.queue:
            i = rid_to_idx[g][q.rid]
            if not admitted[g, i]:
                break
            lst.append(i)
        rq.append(lst)
    v = max(1, max((len(r) for r in rq), default=1))
    arr = np.full((n_g, v), -1, np.int32)
    n = np.zeros(n_g, np.int32)
    for g, lst in enumerate(rq):
        arr[g, :len(lst)] = lst
        n[g] = len(lst)
    return arr, n


def _hold_births(rep, birth, n_g, b):
    """Clear birth rounds of slots whose hold the cut dropped/freed."""
    out = birth.copy()
    for g in range(n_g):
        for s in range(b):
            if s not in rep._holds[g]:
                out[g, s] = -1
    return out
