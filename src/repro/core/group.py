"""The unified Derecho-style ``Group`` API with pluggable protocol backends.

Derecho (the paper's artifact) exposes one handle: a *group* whose
subgroups you ``send()`` into and receive totally-ordered delivery upcalls
from, while every Spindle optimization stays an internal toggle.  This
module is that seam for the repro: one :class:`GroupConfig` describes a
scenario (membership, subgroups, :class:`~repro.core.simulator.SpindleFlags`,
cost/net models) and :meth:`Group.run` executes it unmodified on any of
three substrates behind the :class:`ProtocolBackend` protocol:

  * ``"des"``    — the calibrated discrete-event simulator
                   (:mod:`repro.core.simulator`): answers *how fast* on the
                   paper's RDMA testbed model.
  * ``"graph"``  — the pure-JAX fused predicate sweep
                   (:mod:`repro.core.sweep`): the send pattern is lowered
                   to an ``app_schedule`` array and scanned in-graph.
  * ``"pallas"`` — the graph protocol with the receive predicate evaluated
                   by the fused Pallas SMC-sweep kernel
                   (:mod:`repro.kernels.smc_sweep`) over real slot-counter
                   rings.

Every backend returns the same :class:`RunReport` (throughput, latency
percentiles, app/null delivery accounting, RDMA-write counts) so Fig.
5-style comparisons work like-for-like across substrates, and every
backend records the same per-subgroup total-order delivery log, so
delivered sequences can be asserted identical across backends.

Usage::

    g = Group(cfg)
    h = g.subgroup(0)
    h.ordered_send(sender=0, n=100)
    h.on_delivery(lambda member, msg: ...)
    report = g.run(backend="des")

Reconfiguration across view changes is driven by
:class:`repro.core.views.MembershipService` — see :meth:`Group.reconfigure`.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import (Any, Callable, Deque, Dict, List, Mapping, Optional,
                    Protocol, Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import costmodel, delivery as delivery_mod
from repro.core import desgraph as desgraph_mod
from repro.core import desreplay as desreplay_mod
from repro.core import placement as placement_mod
from repro.core import simulator as sim
from repro.core import sst
from repro.core import sweep as sweep_mod
from repro.core import views as views_mod

Array = Any

# SST row push size (bytes): the coalesced counter row (Sec. 2.2).
_ROW_BYTES = 64


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------

# Re-exported so callers need only `repro.api` / `repro.core.group`.
SubgroupSpec = sim.SubgroupSpec
SpindleFlags = sim.SpindleFlags
SenderPattern = sim.SenderPattern


@dataclasses.dataclass(frozen=True)
class GroupConfig:
    """One multicast scenario, independent of the substrate that runs it."""

    members: Tuple[int, ...]                     # top-level membership
    subgroups: Tuple[sim.SubgroupSpec, ...]
    flags: sim.SpindleFlags = sim.SpindleFlags.spindle()
    net: costmodel.NetworkModel = costmodel.RDMA_CX6
    host: costmodel.HostModel = costmodel.HOST_X86
    patterns: Tuple[Tuple[Tuple[int, int], sim.SenderPattern], ...] = ()
    target_delivered: Optional[int] = None
    max_time_us: float = 60e6
    # DES-plane knobs (charged by the des backend only, carried so a
    # SimConfig round-trips losslessly through the Group API)
    llc_bytes: int = 20 * 1024 * 1024
    upcall_extra_us: float = 0.0
    max_sweeps: int = 3_000_000
    idle_tick_us: float = 2.0
    # graph/pallas round budget; None = auto (max sends + settle rounds)
    rounds: Optional[int] = None
    epoch: int = 0                               # bumped by reconfigure()

    def __post_init__(self):
        members = set(self.members)
        for spec in self.subgroups:
            assert set(spec.members) <= members, \
                f"subgroup members {spec.members} outside group {members}"

    @property
    def n_nodes(self) -> int:
        return max(self.members) + 1 if self.members else 0

    def pattern(self, g: int, node: int) -> sim.SenderPattern:
        for (pg, pn), pat in self.patterns:
            if pg == g and pn == node:
                return pat
        return sim.SenderPattern()

    def to_sim_config(self, **overrides) -> sim.SimConfig:
        """Lower to the DES configuration (the ``des`` backend's input)."""
        kw = dict(n_nodes=self.n_nodes, subgroups=self.subgroups,
                  flags=self.flags, net=self.net, host=self.host,
                  patterns=self.patterns,
                  target_delivered=self.target_delivered,
                  max_time_us=self.max_time_us,
                  llc_bytes=self.llc_bytes,
                  upcall_extra_us=self.upcall_extra_us,
                  max_sweeps=self.max_sweeps,
                  idle_tick_us=self.idle_tick_us)
        kw.update(overrides)
        return sim.SimConfig(**kw)

    @classmethod
    def from_sim_config(cls, cfg: sim.SimConfig, **kw) -> "GroupConfig":
        return cls(members=tuple(range(cfg.n_nodes)),
                   subgroups=cfg.subgroups, flags=cfg.flags, net=cfg.net,
                   host=cfg.host, patterns=cfg.patterns,
                   target_delivered=cfg.target_delivered,
                   max_time_us=cfg.max_time_us,
                   llc_bytes=cfg.llc_bytes,
                   upcall_extra_us=cfg.upcall_extra_us,
                   max_sweeps=cfg.max_sweeps,
                   idle_tick_us=cfg.idle_tick_us, **kw)


def single_group(n_nodes: int, n_senders: Optional[int] = None,
                 msg_size: int = 10240, window: int = 100,
                 n_messages: int = 1000,
                 flags: sim.SpindleFlags = sim.SpindleFlags.spindle(),
                 **kw) -> GroupConfig:
    """One subgroup over ``n_nodes`` nodes — the quickstart scenario."""
    senders = tuple(range(n_senders if n_senders is not None else n_nodes))
    spec = sim.SubgroupSpec(members=tuple(range(n_nodes)), senders=senders,
                            msg_size=msg_size, window=window,
                            n_messages=n_messages)
    return GroupConfig(members=tuple(range(n_nodes)), subgroups=(spec,),
                       flags=flags, **kw)


# ---------------------------------------------------------------------------
# The unified run report
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RunReport:
    """Backend-independent result of one :meth:`Group.run`.

    ``delivered_app_msgs``/``delivered_null_msgs`` are summed over members
    (an app message delivered at k members counts k times, matching the
    simulator's historical accounting); ``nulls_sent`` counts null
    *publishes*.  For the graph/pallas backends the time-domain numbers
    (throughput, latency, duration, rdma_writes) are derived from the same
    calibrated cost model the DES charges, so they are comparable
    like-for-like, not wall-clock measurements.
    """

    backend: str
    throughput_GBps: float
    mean_latency_us: float
    p99_latency_us: float
    duration_us: float
    delivered_app_msgs: int
    delivered_null_msgs: int
    nulls_sent: int
    rdma_writes: int
    rounds: int                         # DES sweeps / graph scan rounds
    per_node_throughput: List[float]
    stalled: bool
    send_batches: List[int] = dataclasses.field(default_factory=list)
    recv_batches: List[int] = dataclasses.field(default_factory=list)
    deliv_batches: List[int] = dataclasses.field(default_factory=list)
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def summary(self) -> Dict[str, Any]:
        return {
            "backend": self.backend,
            "throughput_GBps": round(self.throughput_GBps, 4),
            "mean_latency_us": round(self.mean_latency_us, 3),
            "p99_latency_us": round(self.p99_latency_us, 3),
            "delivered_app_msgs": self.delivered_app_msgs,
            "delivered_null_msgs": self.delivered_null_msgs,
            "nulls_sent": self.nulls_sent,
            "rdma_writes": self.rdma_writes,
            "stalled": self.stalled,
        }


@dataclasses.dataclass(frozen=True)
class Delivery:
    """One delivered application message (nulls never reach upcalls)."""

    subgroup: int
    seq: int                # round-robin sequence number
    sender_rank: int
    sender_index: int       # per-sender publish index (ring index)


@dataclasses.dataclass
class DeliveryLog:
    """The total-order publish log of one subgroup plus how far each
    member's delivery predicate got into it."""

    n_senders: int
    is_app: List[np.ndarray]            # per sender-rank: nullness per index
    delivered_seq: Dict[int, int]       # member node -> highest delivered seq

    def sequence(self, node: int, *, apps_only: bool = True
                 ) -> List[Tuple[int, int, bool]]:
        """Delivered (sender_rank, sender_index, is_app) at ``node`` in
        delivery order."""
        out = []
        for seq in range(self.delivered_seq.get(node, -1) + 1):
            rank, idx = seq % self.n_senders, seq // self.n_senders
            app = bool(idx < len(self.is_app[rank])
                       and self.is_app[rank][idx])
            if app or not apps_only:
                out.append((rank, idx, app))
        return out

    def app_null_counts(self, node: int) -> Tuple[int, int]:
        hi = self.delivered_seq.get(node, -1)
        batch = delivery_mod.DeliveryBatch(lo_seq=0, hi_seq=hi,
                                           n_senders=self.n_senders)
        return delivery_mod.split_app_and_null(batch, self.is_app)

    def app_flags_upto(self, hi: int) -> np.ndarray:
        """Nullness of seqs ``0..hi`` in the total order (False for seqs
        beyond any sender's logged publishes)."""
        flags = np.zeros(max(hi + 1, 0), dtype=bool)
        for r, log in enumerate(self.is_app):
            seqs = np.arange(len(log)) * self.n_senders + r
            m = seqs <= hi
            flags[seqs[m]] = np.asarray(log, dtype=bool)[: len(seqs)][m]
        return flags

    def truncate_to_app_target(self, target: int) -> None:
        """Clip each member's delivered prefix at its ``target``-th app
        message — the logical form of ``target_delivered``'s measurement
        window ("end once every member has delivered this many").  Members
        that overshot the target (the DES stops on simulated time, whole
        batches late; the scan runs a fixed round budget) are cut back to
        the same logical point on every backend, so app sequences stay
        comparable.  A member that delivered exactly ``target`` apps keeps
        its trailing nulls (nothing to cut)."""
        hi_all = max(self.delivered_seq.values(), default=-1)
        if hi_all < 0:
            return
        cum = np.cumsum(self.app_flags_upto(hi_all))
        for node, hi in self.delivered_seq.items():
            if hi >= 0 and cum[hi] > target:
                self.delivered_seq[node] = int(
                    np.searchsorted(cum, target))


# ---------------------------------------------------------------------------
# Backend protocol + registry
# ---------------------------------------------------------------------------


class ProtocolBackend(Protocol):
    """One substrate that can execute a :class:`GroupConfig` scenario."""

    name: str

    def run(self, cfg: GroupConfig,
            counts: Dict[int, np.ndarray]) -> Tuple[RunReport,
                                                    Dict[int, DeliveryLog]]:
        """Execute the scenario.  ``counts[gid]`` is the per-sender-rank
        app-message count for subgroup ``gid``.  Returns the unified report
        plus one delivery log per subgroup."""
        ...


BACKENDS: Dict[str, Callable[[], ProtocolBackend]] = {}


def register_backend(name: str, factory: Callable[[], ProtocolBackend]):
    BACKENDS[name] = factory


def get_backend(backend) -> ProtocolBackend:
    if isinstance(backend, str):
        if backend not in BACKENDS:
            raise KeyError(
                f"unknown backend {backend!r}; have {sorted(BACKENDS)}")
        return BACKENDS[backend]()
    return backend


# ---------------------------------------------------------------------------
# The Group façade
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EpochCarry:
    """What one membership epoch hands the next across the
    virtual-synchrony cut (DESIGN.md Sec. 7).

    Every field is indexed by the NEW view's subgroup ids and sender
    ranks (the closing epoch's ranks remapped through the surviving
    membership).  ``resend[g][s]`` is how many of sender s's app
    messages were underway at the cut — enqueued in the closing epoch
    but not stable at the ragged trim — and must be re-published in the
    new view; per-sender FIFO order is preserved by construction because
    the resend set is the *tail* of that sender's sequence.
    ``stable_apps[g][s]`` is the closing epoch's delta of apps delivered
    everywhere (what the serve plane rebases its slot holds by);
    ``app_base[g][s]`` the cumulative count across ALL prior epochs —
    the global FIFO position of the new epoch's k-th app from s is
    ``app_base[g][s] + k``, and this is the monotone watermark the
    view-change soaks assert never regresses.  ``cut_seq[g]`` is the
    ragged-trim seq in the CLOSING subgroup's total order (diagnostics;
    new-epoch seqs restart at 0)."""

    from_epoch: int
    cut_seq: Tuple[int, ...]
    resend: Tuple[np.ndarray, ...]
    stable_apps: Tuple[np.ndarray, ...]
    app_base: Tuple[np.ndarray, ...]

    def total_resend(self) -> int:
        return int(sum(r.sum() for r in self.resend))


class SubgroupHandle:
    """Send/upcall handle for one subgroup — the Derecho user surface."""

    def __init__(self, group: "Group", gid: int):
        self.group = group
        self.gid = gid

    @property
    def spec(self) -> sim.SubgroupSpec:
        return self.group.cfg.subgroups[self.gid]

    def send(self, sender: Optional[int] = None, n: int = 1) -> None:
        """Queue ``n`` application messages from ``sender`` (a node id;
        defaults to the subgroup's first sender).  Explicit sends take
        over the whole subgroup: they replace the spec's ``n_messages``
        scenario default AND any per-sender pattern budgets — senders you
        do not ``send()`` to send nothing (nulls cover them)."""
        spec = self.spec
        sender = spec.senders[0] if sender is None else sender
        if sender not in spec.senders:
            raise ValueError(f"node {sender} is not a sender of "
                             f"subgroup {self.gid}")
        rank = spec.senders.index(sender)
        self.group._explicit.setdefault(self.gid, np.zeros(
            len(spec.senders), dtype=np.int64))[rank] += n

    # In this repro every send is totally ordered; the two Derecho entry
    # points are therefore the same operation.
    ordered_send = send

    def on_delivery(self, fn: Callable[[int, Delivery], None]) -> None:
        """Register a delivery upcall ``fn(member_node, Delivery)``; fired
        (app messages only, in total order per member) after each run."""
        self.group._upcalls.setdefault(self.gid, []).append(fn)

    def delivered(self, node: int) -> List[Tuple[int, int, bool]]:
        """Delivered (sender_rank, sender_index, is_app) at ``node`` from
        the last run (apps only)."""
        log = self.group.delivery_logs.get(self.gid)
        if log is None:
            raise RuntimeError("run() first")
        return log.sequence(node)


class Group:
    """The one front door: configure once, run on any backend."""

    def __init__(self, cfg: GroupConfig):
        self.cfg = cfg
        self._explicit: Dict[int, np.ndarray] = {}
        self._upcalls: Dict[int, List[Callable]] = {}
        self.delivery_logs: Dict[int, DeliveryLog] = {}
        self.last_report: Optional[RunReport] = None
        # virtual-synchrony epoch carry (set by a cut, consumed by the
        # next epoch's runs/streams — DESIGN.md Sec. 7)
        self.carry: Optional[EpochCarry] = None
        # old gid -> new gid / old->new sender rank maps, populated by
        # reconfigure() on the group it RETURNS (None on fresh groups)
        self._gid_map: Optional[Dict[int, int]] = None
        self._sender_maps: Optional[Dict[int, List[Tuple[int, int]]]] = None

    # -- construction helpers ------------------------------------------------

    @classmethod
    def from_sim_config(cls, cfg: sim.SimConfig, **kw) -> "Group":
        return cls(GroupConfig.from_sim_config(cfg, **kw))

    def subgroup(self, gid: int) -> SubgroupHandle:
        if not 0 <= gid < len(self.cfg.subgroups):
            raise IndexError(gid)
        return SubgroupHandle(self, gid)

    @property
    def n_subgroups(self) -> int:
        return len(self.cfg.subgroups)

    def send_counts(self, gid: int,
                    cfg: Optional[GroupConfig] = None) -> np.ndarray:
        """Effective per-sender-rank app-message counts for one subgroup.

        Explicit queued ``send()`` calls take over the WHOLE subgroup: they
        replace both the spec's ``n_messages`` default and any
        ``SenderPattern.n_messages`` budgets (a sender you did not send()
        to sends nothing).  Without explicit sends, pattern budgets
        override the spec default per sender.  Inactive patterns always
        mask to zero.  A virtual-synchrony ``carry`` (resend counts from
        the previous epoch's cut) is added ON TOP of whatever the above
        computes — resends are obligations of the new view, not scenario
        traffic, so they ride every backend's schedule identically (the
        des/graph/pallas conformance of post-cut runs is free)."""
        cfg = self.cfg if cfg is None else cfg
        spec = cfg.subgroups[gid]
        explicit = self._explicit.get(gid)
        if explicit is not None and len(explicit) != len(spec.senders):
            raise ValueError(
                f"subgroup {gid} has queued explicit sends for "
                f"{len(explicit)} senders but the (overridden) spec has "
                f"{len(spec.senders)}; drop the override or re-queue")
        if explicit is not None:
            counts = explicit.copy()
        else:
            counts = np.full(len(spec.senders), spec.n_messages,
                             dtype=np.int64)
        for rank, node in enumerate(spec.senders):
            pat = cfg.pattern(gid, node)
            if not pat.active:
                counts[rank] = 0
            elif pat.n_messages is not None and explicit is None:
                counts[rank] = pat.n_messages
        if self.carry is not None:
            resend = self.carry.resend[gid]
            if len(resend) != len(spec.senders):
                raise ValueError(
                    f"subgroup {gid} carries resends for {len(resend)} "
                    f"senders but the (overridden) spec has "
                    f"{len(spec.senders)}; a sender-set override cannot "
                    "silently drop the previous epoch's resend set")
            counts = counts + resend.astype(counts.dtype)
        return counts

    # -- running -------------------------------------------------------------

    def run(self, backend="des", **overrides) -> RunReport:
        """Execute the configured scenario on ``backend`` (name or
        :class:`ProtocolBackend` instance) and fire delivery upcalls."""
        cfg = (dataclasses.replace(self.cfg, **overrides) if overrides
               else self.cfg)
        be = get_backend(backend)
        # counts come from the overridden config so per-run overrides to
        # patterns/subgroups behave identically on every backend
        counts = {g: self.send_counts(g, cfg)
                  for g in range(len(cfg.subgroups))}
        report, logs = be.run(cfg, counts)
        self.delivery_logs = logs
        self.last_report = report
        self._fire_upcalls()
        return report

    def run_batch(self, backend="graph", *, windows=None, null_send=None,
                  n_messages=None) -> List[RunReport]:
        """Execute a grid of scenario variants as ONE batched program.

        Each keyword is ``None`` (keep the configured value) or a sequence
        of per-point values; all given grids must share one length B.
        ``windows``/``n_messages`` replace every subgroup's setting at
        that point, ``null_send`` replaces the flag.  On the graph/pallas
        backends the whole grid executes as a single compiled program —
        every point, every subgroup — sharded across ``jax.devices()``
        via shard_map when the batch divides over more than one device
        (plain vmap on a single device; see
        :mod:`repro.core.placement`).  Schedules are padded to a common
        round budget and per-point traces sliced back, producing results
        identical to B sequential :meth:`run` calls — a Fig. 6 window
        sweep or Fig. 11 null-overhead grid becomes one XLA launch
        instead of B Python runs.  Backends without a ``run_batch``
        (e.g. ``des``) fall back to a sequential loop, keeping
        cross-backend conformance testable.

        Returns one :class:`RunReport` per point; each report carries its
        delivery logs in ``extras["delivery_logs"]``.  Delivery upcalls do
        not fire (batch runs are measurement sweeps)."""
        grids = {name: list(vals) for name, vals in
                 (("windows", windows), ("null_send", null_send),
                  ("n_messages", n_messages)) if vals is not None}
        if not grids:
            raise ValueError("run_batch needs at least one grid "
                             "(windows=, null_send= or n_messages=)")
        sizes = {len(v) for v in grids.values()}
        if len(sizes) != 1:
            raise ValueError("grid lengths differ: " + str(
                {k: len(v) for k, v in grids.items()}))
        cfgs = []
        for i in range(sizes.pop()):
            cfg = self.cfg
            over: Dict[str, Any] = {}
            if windows is not None or n_messages is not None:
                over["subgroups"] = tuple(
                    dataclasses.replace(
                        s,
                        window=(int(windows[i]) if windows is not None
                                else s.window),
                        n_messages=(int(n_messages[i])
                                    if n_messages is not None
                                    else s.n_messages))
                    for s in cfg.subgroups)
            if null_send is not None:
                over["flags"] = dataclasses.replace(
                    cfg.flags, null_send=bool(null_send[i]))
            cfgs.append(dataclasses.replace(cfg, **over) if over else cfg)
        counts = [{g: self.send_counts(g, c)
                   for g in range(len(c.subgroups))} for c in cfgs]
        be = get_backend(backend)
        if hasattr(be, "run_batch"):
            results = be.run_batch(cfgs, counts)
        else:
            results = [be.run(c, k) for c, k in zip(cfgs, counts)]
        reports = []
        for report, logs in results:
            report.extras["delivery_logs"] = logs
            reports.append(report)
        return reports

    def stream(self, backend="graph") -> "GroupStream":
        """Open a streaming session over this scenario: feed per-round
        per-sender app-message counts with :meth:`GroupStream.step` (all
        G subgroups sweep as ONE stacked compiled program per round) and
        close with :meth:`GroupStream.finish` for the same
        :class:`RunReport`/delivery logs a scheduled run produces.  This
        is the serve-plane entry point (DESIGN.md Sec. 6): message
        arrivals that only exist at runtime — a decode loop's admissions
        and emitted tokens — ride the multicast substrate round by
        round instead of as a precomputed schedule."""
        return GroupStream(self, backend)

    def _fire_upcalls(self):
        for gid, fns in self._upcalls.items():
            log = self.delivery_logs.get(gid)
            if log is None:
                continue
            spec = self.cfg.subgroups[gid]
            for member in spec.members:
                for rank, idx, _ in log.sequence(member):
                    d = Delivery(subgroup=gid,
                                 seq=idx * log.n_senders + rank,
                                 sender_rank=rank, sender_index=idx)
                    for fn in fns:
                        fn(member, d)

    # -- reconfiguration (virtual synchrony) ---------------------------------

    def reconfigure(self, view: "views_mod.View") -> "Group":
        """Install a new membership view: every subgroup is restricted to
        the surviving members (failed senders drop out; the null-send
        scheme covers them until the view installs).  Returns a fresh
        ``Group`` for the new epoch.

        What crosses the epoch boundary (DESIGN.md Sec. 7): upcall
        registrations, and QUEUED explicit sends — messages handed to
        ``send()`` but never yet underway are the head of the
        virtual-synchrony resend set, remapped to the surviving sender
        ranks (a failed sender's queue dies with it).  Delivery logs do
        NOT carry: each epoch's log is its own total order.  In-flight
        state — messages *published* but not yet stable — is carried by
        the streaming path (:meth:`GroupStream.reconfigure`), which
        computes the cut and installs its resend decision as ``carry``
        on the Group it hands back; scheduled runs of a carried Group
        add those resends to every sender's counts on every backend
        (:meth:`send_counts`)."""
        alive = set(view.members)
        new_specs = []
        gid_map: Dict[int, int] = {}     # old gid -> new gid
        sender_maps: Dict[int, List[Tuple[int, int]]] = {}
        for gid, spec in enumerate(self.cfg.subgroups):
            members = tuple(m for m in spec.members if m in alive)
            senders = tuple(s for s in spec.senders if s in alive)
            if not members:
                continue                 # every member failed: subgroup dies
            sender_maps[gid] = [(spec.senders.index(s), new_rank)
                                for new_rank, s in enumerate(senders)]
            if not senders:
                senders = (members[0],)
            gid_map[gid] = len(new_specs)
            new_specs.append(dataclasses.replace(
                spec, members=members, senders=senders))
        patterns = tuple(((gid_map[g], n), p)
                         for (g, n), p in self.cfg.patterns
                         if g in gid_map and n in alive)
        cfg = dataclasses.replace(
            self.cfg, members=tuple(view.members),
            subgroups=tuple(new_specs), patterns=patterns,
            epoch=self.cfg.epoch + 1)
        g = Group(cfg)
        g._upcalls = {gid_map[gid]: list(fns)
                      for gid, fns in self._upcalls.items()
                      if gid in gid_map}
        for gid, new_gid in gid_map.items():
            queued = self._explicit.get(gid)
            if queued is None:
                continue
            remapped = np.zeros(len(new_specs[new_gid].senders), np.int64)
            for old_rank, new_rank in sender_maps[gid]:
                remapped[new_rank] = queued[old_rank]
            if remapped.any():
                g._explicit[new_gid] = remapped
        g._gid_map = gid_map
        g._sender_maps = sender_maps
        return g


# ---------------------------------------------------------------------------
# "des" / "des-loop" backends — the discrete-event simulator.  "des" is
# the two-phase simulate-then-execute split (DESIGN.md Sec. 12):
# repro.core.desgraph timestamps the event timeline, repro.core.desreplay
# replays the emitted graph.  "des-loop" is the legacy single-phase
# event loop, kept for differential testing — both produce bit-identical
# results by construction.
# ---------------------------------------------------------------------------


def _des_logs(groups) -> Dict[int, DeliveryLog]:
    """Delivery logs from final per-subgroup DES state (either phase-1
    ``DesGraph.groups`` or the legacy ``Simulator.groups``)."""
    logs = {}
    for g in groups:
        is_app = [~np.isnan(g.gen_log[s][: int(g.gen_len[s])])
                  for s in range(g.n_s)]
        delivered = {node: int(g.deliv_seen[g.member_pos[node],
                                            g.member_pos[node]])
                     for node in g.spec.members}
        logs[g.gid] = DeliveryLog(n_senders=g.n_s, is_app=is_app,
                                  delivered_seq=delivered)
    return logs


def _des_report(name: str, cfg: GroupConfig, result: sim.SimResult,
                groups) -> Tuple[RunReport, Dict[int, DeliveryLog]]:
    """Shared DES report assembly — both the two-phase ``des`` path and
    the legacy ``des-loop`` lower their :class:`SimResult` + final group
    state through this, so bit-identity between them is a statement
    about the simulators, not the reporting glue."""
    logs = _des_logs(groups)
    if cfg.target_delivered is not None:
        for log in logs.values():
            log.truncate_to_app_target(cfg.target_delivered)
    # app/null accounting comes from the (possibly clipped) delivery
    # logs so it always matches what delivered()/upcalls expose;
    # throughput/latency stay the DES's timing truths.
    n_app, n_null = _sum_delivered(logs)
    report = RunReport(
        backend=name,
        throughput_GBps=result.throughput_GBps,
        mean_latency_us=result.mean_latency_us,
        p99_latency_us=result.p99_latency_us,
        duration_us=result.duration_us,
        delivered_app_msgs=n_app,
        delivered_null_msgs=n_null,
        nulls_sent=result.nulls_sent,
        rdma_writes=result.rdma_writes,
        rounds=result.sweeps,
        per_node_throughput=result.per_node_throughput,
        stalled=result.stalled,
        send_batches=result.send_batches,
        recv_batches=result.recv_batches,
        deliv_batches=result.deliv_batches,
        extras={"post_time_us": result.post_time_us,
                "predicate_time_us": result.predicate_time_us,
                "sender_blocked_us": result.sender_blocked_us},
    )
    return report, logs


class DESLoopBackend:
    """The legacy single-phase DES event loop (``des-loop``), retained
    for differential testing of the two-phase ``des`` path
    (DESIGN.md Sec. 12).  Not streamable — use ``des`` for that."""

    name = "des-loop"

    def run(self, cfg: GroupConfig, counts: Dict[int, np.ndarray]
            ) -> Tuple[RunReport, Dict[int, DeliveryLog]]:
        sim_cfg = self._lower(cfg, counts)
        simulator = sim.Simulator(sim_cfg)
        result = simulator.run()
        return _des_report(self.name, cfg, result, simulator.groups)

    @staticmethod
    def _lower(cfg: GroupConfig, counts: Dict[int, np.ndarray]
               ) -> sim.SimConfig:
        """Per-sender counts lower to ``SenderPattern.n_messages``
        overrides (count 0 = inactive)."""
        patterns = {(g, n): p for (g, n), p in cfg.patterns}
        specs = []
        for gid, spec in enumerate(cfg.subgroups):
            c = counts[gid]
            specs.append(dataclasses.replace(
                spec, n_messages=int(c.max()) if len(c) else 0))
            for rank, node in enumerate(spec.senders):
                base = patterns.get((gid, node), sim.SenderPattern())
                patterns[(gid, node)] = dataclasses.replace(
                    base, active=base.active and int(c[rank]) > 0,
                    n_messages=int(c[rank]))
        return cfg.to_sim_config(
            subgroups=tuple(specs),
            patterns=tuple(patterns.items()))


# ---------------------------------------------------------------------------
# "graph" / "pallas" backends — the fused STACKED sweep: one compiled
# program per whole-group scenario shape (all subgroups padded + masked),
# one device-sharded program per scenario grid
# ---------------------------------------------------------------------------

# One entry is appended per TRACE of a stacked program (jit runs the
# Python body only while compiling): the padded stack shape
# (G, N_max, S_max), the per-subgroup window tuple, and the backend name.
# The hot-path tests assert that a repeated Group.run with the same static
# key leaves this list untouched, the stacked tests that a G-subgroup run
# appends exactly ONE entry, and the view-change soaks that a
# shape-preserving reconfigure appends NONE (the per-subgroup sizes are
# traced validity masks, not part of the key).
#
# Bounded: a long-lived open-loop process (the workload plane drives
# streams for hours — DESIGN.md Sec. 10) would otherwise grow this list
# by one entry per distinct compile forever.  The cap is far above any
# real session's distinct-shape count, so the delta assertions above are
# unaffected; use :func:`trace_snapshot` / :func:`trace_reset` (also
# re-exported from :mod:`repro.api`) rather than touching the deque.
TRACE_MAXLEN = 4096
TRACE_EVENTS: Deque[Tuple[Tuple[int, ...], Tuple[int, ...], str]] = \
    collections.deque(maxlen=TRACE_MAXLEN)


def trace_snapshot() -> Tuple[Tuple[Tuple[int, ...], Tuple[int, ...], str],
                              ...]:
    """Immutable copy of the compile-trace history (newest last).  The
    supported way to measure "how many programs did this sweep trace":
    take a snapshot before, subtract its length after."""
    return tuple(TRACE_EVENTS)


def trace_reset() -> int:
    """Clear the compile-trace history; returns how many entries were
    dropped.  Does NOT evict compiled programs — a cleared history only
    forgets that past traces happened."""
    n = len(TRACE_EVENTS)
    TRACE_EVENTS.clear()
    return n


def _lower_schedule(counts: np.ndarray, rounds: int) -> np.ndarray:
    """(S,) per-sender counts -> (T, S) app_schedule: one message per
    active round until each sender's budget is spent."""
    t = np.arange(rounds)[:, None]
    return (t < counts[None, :]).astype(np.int32)


def _cost_params(cfg: GroupConfig, spec: sim.SubgroupSpec) -> np.ndarray:
    """Lower the per-round cost model to six coefficients consumed as
    vectorized in-graph arithmetic by :func:`_fold_cost`:
    ``[base, post, per_msg, wire, row_writes, peers]``.

    Per round every member pushes its SST row (one coalesced 64 B write per
    peer, the ``base`` term); a sender that published ``k`` app messages
    additionally pushes them as one batched slot write of ``k`` slots per
    peer (the Sec. 3.2 batch-send path: ``post + per_msg * k``).  The round
    takes as long as the busiest node's post+serialization charge plus one
    wire hop — the same calibrated constants the DES charges, so
    graph/pallas reports are comparable like-for-like with the ``des``
    backend.  ``row_writes`` (= n*(n-1)) and ``peers`` (= n-1) carry the
    membership size into the fold so one shape-agnostic fold serves every
    subgroup of a padded stack.
    """
    n = len(spec.members)
    if n <= 1:
        return np.zeros(6)
    slot = spec.msg_size + 8
    host, net = cfg.host, cfg.net
    base = host.lock_us + 3 * host.predicate_eval_us + \
        (n - 1) * (net.post_us + net.serialization(_ROW_BYTES))
    return np.array([base,
                     (n - 1) * net.post_us,
                     (n - 1) * net.serialization(slot),
                     net.wire_latency(min(slot, 4096)),
                     n * (n - 1),
                     n - 1])


def _fold_cost(app_pub, cost):
    """The cost model as vectorized in-graph arithmetic over the (T, S)
    publish trace: (app_pub, cost coefficients) -> per-round time + RDMA
    writes arrays.  Shape-agnostic in the membership size (carried in the
    coefficients), so it vmaps over subgroup stacks and scenario grids."""
    # Busiest sender per round: serialization is linear in k, so the
    # max-k sender is the argmax of post + per_msg * k.
    kmax = jnp.max(app_pub, axis=1)                            # (T,)
    busiest = jnp.where(kmax > 0, cost[1] + cost[2] * kmax, 0.0)
    round_t = cost[0] + busiest + cost[3]                      # (T,)
    round_w = cost[4].astype(jnp.int32) + cost[5].astype(jnp.int32) * \
        jnp.sum((app_pub > 0).astype(jnp.int32), axis=1)       # (T,)
    return round_t, round_w


# Jitted once: _aggregate folds every stream's (G, T, S) trace through
# this on the host path, and an eager vmap would re-trace per call —
# measurably slower than the fold itself on serve-plane shapes.
_fold_cost_stacked = jax.jit(jax.vmap(_fold_cost))


def fold_cost_np(app_pub: np.ndarray, cost: np.ndarray) -> np.ndarray:
    """Host-side mirror of :func:`_fold_cost`'s time term over one
    subgroup's (T, S) publish trace -> (T,) per-round microseconds.
    Kept adjacent to the in-graph fold so the two cannot drift; the
    workload plane's latency accountant (DESIGN.md Sec. 10) uses it to
    convert round-granular latencies into the calibrated cost model's
    time units without re-entering jax."""
    app_pub = np.asarray(app_pub)
    kmax = app_pub.max(axis=1) if app_pub.size else \
        np.zeros(app_pub.shape[0])
    busiest = np.where(kmax > 0, cost[1] + cost[2] * kmax, 0.0)
    return cost[0] + busiest + cost[3]


def _kernel_receive(ring_window: int):
    """Receive-predicate override for the pallas backend: the fused
    watermark kernel sweeps every (member, sender) ring in one call,
    rebuilding the counter tile inside the kernel — nothing (N*S, W)-shaped
    is materialized in-graph per round.  ``ring_window`` is the static ring
    width (the max window across a stacked group / batched grid); a ring
    wider than a subgroup's protocol window is harmless — slots are only
    reused after W messages and the publish cap uses the per-subgroup
    window.  ``valid`` masks padded (member, sender) lanes of a stacked
    subgroup plane (None when unpadded)."""
    from repro.kernels import ops

    def receive(pub_vis, recv_counts, valid=None):
        n_m, n_s = pub_vis.shape
        flat_valid = None if valid is None else valid.reshape(n_m * n_s)
        visible = ops.smc_sweep_watermark(
            pub_vis.reshape(n_m * n_s), recv_counts.reshape(n_m * n_s),
            window=ring_window, valid=flat_valid)
        return jnp.maximum(
            recv_counts,
            visible.reshape(n_m, n_s).astype(recv_counts.dtype))

    return receive


def _stack_masks(members: Tuple[int, ...], senders: Tuple[int, ...]):
    """(G, N_max)/(G, S_max) suffix-padding validity masks — or
    ``(None, None)`` for a homogeneous stack (every subgroup fills the
    padded shape), which keeps the cheaper unmasked sweep arithmetic on
    the G=1 and equal-sized-topics hot paths."""
    n_max, s_max = max(members), max(senders)
    member_masks = np.arange(n_max)[None, :] < np.asarray(members)[:, None]
    sender_masks = np.arange(s_max)[None, :] < np.asarray(senders)[:, None]
    if member_masks.all() and sender_masks.all():
        return None, None
    return member_masks, sender_masks


@functools.lru_cache(maxsize=None)
def _scan_program(n_subgroups: int, n_max: int, s_max: int,
                  windows: Tuple[int, ...], masked: bool, null_send: bool,
                  backend: str):
    """Compile-once STACKED program for one whole-group scenario shape,
    cached on the PADDED stack shape ``(G, N_max, S_max)`` plus the
    per-subgroup windows and ``(null_send, backend)`` — the unit of
    compilation is the group, not the subgroup: all G subgroups execute
    as one fused program (:func:`sweep.run_stacked`), with the cost
    model folded in as vectorized in-graph arithmetic.

    The exact per-subgroup member/sender sizes are NOT in the key: when
    ``masked``, they enter as traced ``(G, N_max)``/``(G, S_max)``
    validity-mask inputs, so a view change that re-shapes subgroups
    inside an unchanged padded stack — a member fails in one subgroup
    while another still sets N_max — reuses the compiled program instead
    of re-stacking from scratch (DESIGN.md Sec. 7).  Repeated
    ``Group.run`` calls and benchmark sweeps reuse the jitted program
    instead of re-tracing it.  (jax additionally keys on the schedule
    shape, so a different round budget recompiles — same scenario, same
    program.)"""
    ring = max(windows) if backend == "pallas" else 0
    receive_fn = _kernel_receive(ring) if backend == "pallas" else None
    win_arr = np.asarray(windows, np.int32)

    def fn(scheds, costs, *masks):
        TRACE_EVENTS.append(((n_subgroups, n_max, s_max), windows,
                             backend))
        mm, sm = masks if masked else (None, None)
        states = sweep_mod.batch_states(n_max, s_max, n_subgroups)
        _, (batches, app_pub, nulls) = sweep_mod.run_stacked(
            states, scheds, windows=win_arr, null_send=null_send,
            member_masks=mm, sender_masks=sm,
            receive_fn=receive_fn)
        round_t, round_w = jax.vmap(_fold_cost)(app_pub, costs)
        return batches, app_pub, nulls, round_t, round_w

    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _batch_program(members: Tuple[int, ...], senders: Tuple[int, ...],
                   ring_window: int, backend: str, n_shards: int):
    """Compile-once BATCHED stacked program: B grid points x G subgroups
    as one device-sharded compiled program.  Windows and null-send flags
    are per-point traced values; ``ring_window`` (the common SMC ring
    width, max of the grid) only matters to the pallas receive kernel (the
    graph backend passes 0 so one cache entry serves every grid).  When
    ``n_shards > 1`` the leading grid axis is shard_mapped across devices
    (:func:`repro.core.placement.shard_over_batch`); on a single device it
    degrades to the plain vmapped program."""
    receive_fn = _kernel_receive(ring_window) if backend == "pallas" \
        else None
    n_max, s_max = max(members), max(senders)
    member_masks, sender_masks = _stack_masks(members, senders)

    def fn(scheds, windows, null_sends, costs):
        TRACE_EVENTS.append((members, senders, backend))
        b = scheds.shape[0]
        states = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (b,) + x.shape),
            sweep_mod.batch_states(n_max, s_max, len(members)))
        _, (batches, app_pub, nulls) = sweep_mod.run_stacked_batch(
            states, scheds, windows=windows, null_sends=null_sends,
            member_masks=member_masks, sender_masks=sender_masks,
            receive_fn=receive_fn)
        round_t, round_w = jax.vmap(jax.vmap(_fold_cost))(app_pub, costs)
        return batches, app_pub, nulls, round_t, round_w

    if n_shards > 1:
        fn = placement_mod.shard_over_batch(fn, n_shards, n_batched_args=4)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _stream_program(n_subgroups: int, n_max: int, s_max: int,
                    windows: Tuple[int, ...], masked: bool,
                    null_send: bool, backend: str):
    """Compile-once STREAMING program: ONE protocol round for all G
    subgroups of a scenario shape, carrying (states, backlogs) across
    calls.  Same padded-shape static key and same masked stacking as
    :func:`_scan_program` — so a stream that survives a shape-preserving
    view change (:meth:`GroupStream.reconfigure`) keeps dispatching the
    SAME compiled program in the new epoch; the round arithmetic is the
    scan body itself (:func:`repro.core.sweep.step_backlog`), so T
    streamed rounds are bit-identical to one T-round scan fed the same
    ready rows.  A whole streamed session — however many rounds, across
    however many same-shape epochs — traces exactly once."""
    ring = max(windows) if backend == "pallas" else 0
    receive_fn = _kernel_receive(ring) if backend == "pallas" else None
    win_arr = np.asarray(windows, np.int32)

    # named so that its XLA module is ``jit_spindle_stream_round`` in a
    # profiler trace, apart from the scan and batch programs
    def spindle_stream_round(states, backlogs, ready, *masks):
        TRACE_EVENTS.append(((n_subgroups, n_max, s_max), windows,
                             backend))
        mm, sm = masks if masked else (None, None)
        return sweep_mod.stream_stacked(
            states, backlogs, ready, windows=win_arr, null_send=null_send,
            member_masks=mm, sender_masks=sm,
            receive_fn=receive_fn)

    return jax.jit(spindle_stream_round)


# Programs that EMBED the stream round body inside a larger compiled
# loop (e.g. the fused serve plane: decode + multicast sweep + watermark
# gating scanned device-resident, repro.serve.fused).  Keyed by the
# caller's full static tuple — scenario shape AND whatever the fused
# body bakes in (model config, round budgets) — so a warm run is pure
# dispatch: same workload shape, same program, zero re-traces.  The
# builder appends its own TRACE_EVENTS entry when traced, exactly like
# _scan_program/_stream_program, so the bench's one-program assertions
# cover fused runs too.
_FUSED_PROGRAMS: Dict[Tuple, Any] = {}


def fused_stream_program(key: Tuple, build: Callable[[], Any]):
    """Compile-once cache for stream-composed fused programs.  ``key``
    must be a hashable static description of everything ``build()``'s
    program closes over; ``build`` is called once per key and must
    return the jitted program."""
    prog = _FUSED_PROGRAMS.get(key)
    if prog is None:
        prog = _FUSED_PROGRAMS[key] = build()
    return prog


@dataclasses.dataclass
class _GraphAgg:
    """Accumulates one run's subgroup post-processing into report inputs."""

    duration: float = 0.0
    writes: int = 0
    delivered_app: int = 0
    delivered_null: int = 0
    nulls_sent: int = 0
    rounds: int = 0
    stalled: bool = False
    latencies: List[float] = dataclasses.field(default_factory=list)
    per_node_bytes: Dict[int, float] = dataclasses.field(
        default_factory=dict)
    logs: Dict[int, DeliveryLog] = dataclasses.field(default_factory=dict)


class GraphBackend:
    """Runs the scenario through :func:`repro.core.sweep.run_stacked`
    under a cached jitted program (see :func:`_scan_program`) whose unit
    of compilation is the whole GROUP: all G subgroups, padded to a
    common (G, N_max, S_max) with validity masks, execute as one fused
    program with the cost model evaluated in-graph; delivery logs and
    latency round-pairs are then reconstructed per subgroup from the
    sliced per-round traces with vectorized numpy.  :meth:`run_batch`
    executes whole scenario grids as ONE compiled program, shard_mapped
    across devices when more than one is available."""

    name = "graph"

    @staticmethod
    def _rounds_for(cfg: GroupConfig, spec: sim.SubgroupSpec,
                    counts: np.ndarray) -> int:
        """Round budget: settle rounds for visibility/null drain, plus
        slack for ring-window throttling (a small window stretches
        publishing over ~3 extra rounds per window-full of backlog)."""
        if cfg.rounds is not None:
            return cfg.rounds
        max_c = int(counts.max()) if len(counts) else 0
        return max_c + 2 * len(spec.members) + 8 + \
            3 * (max_c // max(spec.window, 1))

    # -- stacking: one group scenario -> padded program inputs ---------------

    def _stack(self, cfg: GroupConfig, counts: Dict[int, np.ndarray]):
        """Lower one scenario to the stacked program's static key and
        padded inputs: per-subgroup shape tuples, round budgets, a
        (G, T_max, S_max) schedule stack and (G, 6) cost coefficients."""
        members = tuple(len(s.members) for s in cfg.subgroups)
        senders = tuple(len(s.senders) for s in cfg.subgroups)
        windows = tuple(s.window for s in cfg.subgroups)
        rounds = tuple(self._rounds_for(cfg, spec, counts[g])
                       for g, spec in enumerate(cfg.subgroups))
        t_max, s_max = max(rounds), max(senders)
        scheds = np.zeros((len(members), t_max, s_max), np.int32)
        for g in range(len(members)):
            scheds[g, :, : senders[g]] = _lower_schedule(counts[g], t_max)
        costs = np.stack([_cost_params(cfg, spec)
                          for spec in cfg.subgroups]).astype(np.float32)
        return members, senders, windows, rounds, scheds, costs

    def program(self, cfg: GroupConfig, counts: Dict[int, np.ndarray]):
        """The stacked scan program :meth:`run` dispatches for one
        scenario, its device inputs and the per-subgroup round budgets
        (``program.lower(*args)`` shows what the device runs)."""
        members, senders, windows, rounds, scheds, costs = \
            self._stack(cfg, counts)
        member_masks, sender_masks = _stack_masks(members, senders)
        masked = member_masks is not None
        program = _scan_program(len(members), max(members), max(senders),
                                windows, masked, cfg.flags.null_send,
                                self.name)
        args = [jnp.asarray(scheds), jnp.asarray(costs)]
        if masked:
            args += [jnp.asarray(member_masks), jnp.asarray(sender_masks)]
        return program, args, rounds

    def run(self, cfg: GroupConfig, counts: Dict[int, np.ndarray]
            ) -> Tuple[RunReport, Dict[int, DeliveryLog]]:
        agg = _GraphAgg()
        if cfg.subgroups:
            program, args, rounds = self.program(cfg, counts)
            outs = [np.asarray(o) for o in program(*args)]
            self._finalize(cfg, counts, outs, rounds, agg)
        return self._report(agg), agg.logs

    def run_batch(self, cfgs: List[GroupConfig],
                  counts_list: List[Dict[int, np.ndarray]]
                  ) -> List[Tuple[RunReport, Dict[int, DeliveryLog]]]:
        """Execute B scenario variants as ONE compiled stacked program —
        every grid point, every subgroup, one dispatch — sharded over
        ``jax.devices()`` when the batch divides across more than one
        (vmap on a single device).  All points must share membership
        shapes (n_members, n_senders per subgroup); schedules are padded
        to the common round budget and each point's traces sliced back to
        its own budget afterwards, so every point's results are identical
        to a sequential :meth:`run` of that point — the scan prefix
        depends only on the schedule prefix."""
        if not cfgs:
            return []
        base = cfgs[0]
        for i, cfg in enumerate(cfgs[1:], start=1):
            if len(cfg.subgroups) != len(base.subgroups):
                raise ValueError(
                    f"run_batch points must share membership shapes; grid "
                    f"point {i} has {len(cfg.subgroups)} subgroups, grid "
                    f"point 0 has {len(base.subgroups)}")
            for gid, (s0, si) in enumerate(zip(base.subgroups,
                                               cfg.subgroups)):
                if (len(si.members) != len(s0.members)
                        or len(si.senders) != len(s0.senders)):
                    raise ValueError(
                        "run_batch points must share membership shapes; "
                        f"subgroup {gid} at grid point {i} has "
                        f"{len(si.members)} members / {len(si.senders)} "
                        f"senders vs grid point 0's {len(s0.members)} / "
                        f"{len(s0.senders)}")
        b = len(cfgs)
        stacks = [self._stack(cfg, counts_list[i])
                  for i, cfg in enumerate(cfgs)]
        members, senders = stacks[0][0], stacks[0][1]
        t_max = max(max(st[3]) for st in stacks)
        s_max = max(senders)
        scheds = np.zeros((b, len(members), t_max, s_max), np.int32)
        for i, st in enumerate(stacks):
            scheds[i, :, : st[4].shape[1]] = st[4]
        windows = np.asarray([st[2] for st in stacks], np.int32)  # (B, G)
        nulls_on = np.asarray([cfg.flags.null_send for cfg in cfgs])
        costs = np.stack([st[5] for st in stacks])                # (B, G, 6)
        ring = int(windows.max()) if self.name == "pallas" else 0
        n_shards = placement_mod.shard_count(b)
        program = _batch_program(members, senders, ring, self.name,
                                 n_shards)
        raw = program(jnp.asarray(scheds), jnp.asarray(windows),
                      jnp.asarray(nulls_on), jnp.asarray(costs))
        # where the grid actually ran: the devices holding its output
        n_devices = len(raw[0].sharding.device_set)
        outs = [np.asarray(o) for o in raw]
        results = []
        for i in range(b):
            agg = _GraphAgg()
            self._finalize(cfgs[i], counts_list[i],
                           [o[i] for o in outs], stacks[i][3], agg)
            report = self._report(agg)
            report.extras["batch_devices"] = n_devices
            results.append((report, agg.logs))
        return results

    # -- host-side post-processing -------------------------------------------

    def _finalize(self, cfg: GroupConfig, counts: Dict[int, np.ndarray],
                  outs: List[np.ndarray], rounds: Tuple[int, ...],
                  agg: _GraphAgg) -> None:
        """Slice one run's stacked (G, T_max, ...) traces back to each
        subgroup's own round budget and real membership, reconstruct the
        delivery logs, apply the target-delivered measurement window, and
        accumulate report inputs."""
        parts = []
        for gid, spec in enumerate(cfg.subgroups):
            n_g, s_g, t_g = len(spec.members), len(spec.senders), rounds[gid]
            point = [outs[0][gid, :t_g, :n_g], outs[1][gid, :t_g, :s_g],
                     outs[2][gid, :t_g, :s_g], outs[3][gid, :t_g],
                     outs[4][gid, :t_g]]
            log, lat = self._reconstruct(spec, point[0], point[1], point[2])
            parts.append((gid, spec, point, log, lat))
        cross_target = (cfg.target_delivered is not None
                        and len(cfg.subgroups) > 1)
        if cfg.target_delivered is not None:
            if cross_target:
                _clip_target_stacked(cfg, parts)
            else:
                parts[0][3].truncate_to_app_target(cfg.target_delivered)
        for gid, spec, point, log, lat in parts:
            self._account(cfg, spec, gid, counts[gid], rounds[gid], point,
                          log, lat, agg,
                          per_subgroup_stall=not cross_target)
        if cross_target:
            agg.stalled = agg.stalled or _stalled_across_subgroups(
                cfg, counts, agg.logs)

    def _account(self, cfg: GroupConfig, spec: sim.SubgroupSpec,
                 gid: int, c: np.ndarray, rounds: int,
                 arrays: List[np.ndarray], log: DeliveryLog,
                 lat_pairs: np.ndarray, agg: _GraphAgg, *,
                 per_subgroup_stall: bool = True) -> None:
        """Accumulate one subgroup's post-processed traces into the
        report inputs."""
        batches, app_pub, nulls, round_t, round_w = arrays
        agg.logs[gid] = log
        agg.rounds += rounds
        agg.nulls_sent += int(nulls.sum())
        agg.writes += int(round_w.astype(np.int64).sum())
        end_time = np.cumsum(round_t.astype(np.float64))
        if rounds:
            agg.duration = max(agg.duration, float(end_time[-1]))
        if len(lat_pairs):
            pr, dr = lat_pairs[:, 0], lat_pairs[:, 1]
            start = np.where(pr > 0, end_time[np.maximum(pr - 1, 0)], 0.0)
            agg.latencies.extend((end_time[dr] - start).tolist())
        for node in spec.members:
            a, nl = log.app_null_counts(node)
            agg.delivered_app += a
            agg.delivered_null += nl
            agg.per_node_bytes[node] = \
                agg.per_node_bytes.get(node, 0.0) + a * spec.msg_size
        if per_subgroup_stall:
            total_app = int(c.sum())
            need = total_app if cfg.target_delivered is None else \
                min(cfg.target_delivered, total_app)
            if any(log.app_null_counts(node)[0] < need
                   for node in spec.members):
                agg.stalled = True

    def _report(self, agg: _GraphAgg) -> RunReport:
        per_node = [b / agg.duration / 1e3
                    for b in agg.per_node_bytes.values()
                    if agg.duration > 0 and b > 0]
        lat = np.array(agg.latencies) if agg.latencies else np.array([0.0])
        return RunReport(
            backend=self.name,
            throughput_GBps=float(np.mean(per_node)) if per_node else 0.0,
            mean_latency_us=float(lat.mean()),
            p99_latency_us=float(np.percentile(lat, 99)),
            duration_us=agg.duration,
            delivered_app_msgs=agg.delivered_app,
            delivered_null_msgs=agg.delivered_null,
            nulls_sent=agg.nulls_sent,
            rdma_writes=agg.writes,
            rounds=agg.rounds,
            per_node_throughput=per_node,
            stalled=agg.stalled,
        )

    @staticmethod
    def _reconstruct(spec: sim.SubgroupSpec, batches: np.ndarray,
                     app_pub: np.ndarray, nulls: np.ndarray):
        """Rebuild the per-sender nullness log and (publish_round,
        delivery_round) latency samples from the per-round trace, fully
        vectorized (``repeat``/``cumsum``/``searchsorted`` — no
        per-message Python loop).  Within a round a sender publishes its
        app messages before its nulls (matching :func:`sweep.sweep`'s
        ``published + app_pub + nulls``).  Returns the log plus a (K, 2)
        int array of latency round-pairs sampled at member position 0
        (as the DES does)."""
        n_s = len(spec.senders)
        rounds = batches.shape[0]
        is_app: List[np.ndarray] = []
        pub_round: List[np.ndarray] = []
        for s in range(n_s):
            a = app_pub[:, s].astype(np.int64)
            total = a + nulls[:, s].astype(np.int64)
            rnd = np.repeat(np.arange(rounds), total)
            start = np.cumsum(total) - total          # exclusive prefix
            offset = np.arange(total.sum()) - np.repeat(start, total)
            is_app.append(offset < np.repeat(a, total))
            pub_round.append(rnd)
        delivered_num = np.cumsum(batches, axis=0) - 1   # (T, N)
        final = delivered_num[-1] if rounds else \
            np.full(len(spec.members), -1)
        delivered = {node: int(final[pos])
                     for pos, node in enumerate(spec.members)}
        lat = np.zeros((0, 2), np.int64)
        if rounds and int(final[0]) >= 0:
            col = delivered_num[:, 0]
            seqs = np.arange(int(final[0]) + 1)
            ranks, idxs = seqs % n_s, seqs // n_s
            maxlen = max(len(x) for x in is_app)
            flags = np.zeros((n_s, maxlen), bool)
            rnds = np.zeros((n_s, maxlen), np.int64)
            for s in range(n_s):
                flags[s, : len(is_app[s])] = is_app[s]
                rnds[s, : len(pub_round[s])] = pub_round[s]
            m = flags[ranks, idxs]
            lat = np.stack([rnds[ranks[m], idxs[m]],
                            np.searchsorted(col, seqs[m])], axis=1)
        log = DeliveryLog(n_senders=n_s, is_app=is_app,
                          delivered_seq=delivered)
        return log, lat


def _clip_target_stacked(cfg: GroupConfig, parts) -> None:
    """Apply the ``target_delivered`` measurement window to a
    multi-subgroup stacked run.

    The stacked program executes every subgroup on ONE shared round
    timeline, so — like the DES's per-member aggregate across subgroups
    (``Simulator._done``) — the window is cross-subgroup: for each member,
    find the earliest shared round at which its app deliveries summed over
    its subgroups reach the target, clip each subgroup's delivered prefix
    for that member to its value at that round, then clip within-subgroup
    overshoot at the target exactly as the des backend does.  The des
    backend stops on simulated time (whole batches late, per-subgroup
    interleaving timing-dependent), so cross-backend conformance here is
    prefix-consistency of each subgroup's total order plus the target
    guarantee — not bit-identical cut points (those are only guaranteed
    between graph/pallas runs and against sequential stacked runs)."""
    target = cfg.target_delivered
    per_member: Dict[int, List[Tuple[DeliveryLog, int, np.ndarray,
                                     np.ndarray]]] = {}
    for gid, spec, point, log, lat in parts:
        batches = point[0]
        if not len(batches):
            continue
        delivered_num = np.cumsum(batches.astype(np.int64), axis=0) - 1
        hi = int(delivered_num.max(initial=-1))
        # app_cum[k] = app messages among the first k seqs of the order
        app_cum = np.concatenate(
            [[0], np.cumsum(log.app_flags_upto(hi))]).astype(np.int64)
        for pos, node in enumerate(spec.members):
            col = delivered_num[:, pos]                       # (t_g,)
            apps = app_cum[col + 1]         # apps delivered by round r
            per_member.setdefault(node, []).append((log, node, col, apps))
    for node, entries in per_member.items():
        t_shared = max(len(col) for _, _, col, _ in entries)
        total = np.zeros(t_shared, np.int64)
        for _, _, col, apps in entries:
            pad = t_shared - len(apps)
            total += np.concatenate(
                [apps, np.full(pad, apps[-1] if len(apps) else 0)])
        hit = np.nonzero(total >= target)[0]
        if not len(hit):
            continue                     # target never reached: keep all
        cut = int(hit[0])
        for log, node_, col, _ in entries:
            log.delivered_seq[node_] = int(col[min(cut, len(col) - 1)])
    for gid, spec, point, log, lat in parts:
        log.truncate_to_app_target(target)


def _stalled_across_subgroups(cfg: GroupConfig,
                              counts: Dict[int, np.ndarray],
                              logs: Mapping[int, DeliveryLog]) -> bool:
    """Multi-subgroup target_delivered stall check: a member stalls when
    its app deliveries summed over its subgroups fall short of the target
    (capped by what its subgroups could supply at all)."""
    delivered: Dict[int, int] = {}
    avail: Dict[int, int] = {}
    for gid, spec in enumerate(cfg.subgroups):
        total_app = int(counts[gid].sum())
        for node in spec.members:
            delivered[node] = delivered.get(node, 0) + \
                logs[gid].app_null_counts(node)[0]
            avail[node] = avail.get(node, 0) + total_app
    return any(delivered[node] < min(cfg.target_delivered, avail[node])
               for node in delivered)


class PallasBackend(GraphBackend):
    """The graph protocol with the receive predicate evaluated by the
    fused Pallas SMC-sweep kernel — the structural analogue of keeping the
    SMC polling area cache-resident.  The kernel consumes per-sender
    published watermarks and rebuilds the slot-counter tile inside the
    kernel (:func:`repro.kernels.smc_sweep.smc_sweep_watermark_pallas`),
    so the hot loop no longer materializes the (N*S, W) ring in-graph
    every round; it compiles to Mosaic on TPU and interprets elsewhere.
    In a stacked multi-subgroup program the kernel sweeps the padded
    (member, sender) plane of every subgroup with an explicit lane
    validity mask.  The receive closure is installed by
    :func:`_kernel_receive` via the cached scan programs."""

    name = "pallas"


class DESBackend(GraphBackend):
    """The two-phase DES (DESIGN.md Sec. 12) — the default ``des`` path.

    Scheduled runs execute phase 1 (:func:`repro.core.desgraph.simulate`,
    the slimmed event-level pass emitting the compact event graph) then
    phase 2 (:func:`repro.core.desreplay.replay`, the vectorized
    reconstruction), bit-identical to the legacy ``des-loop`` — that
    split is what makes 256–4096-node fleets conformance-testable.

    Streaming (:class:`GroupStream`) runs on the numpy round mirror
    (``stream_numpy``): the same :func:`repro.core.sweep.step_backlog`
    arithmetic evaluated host-side in int32, driven through the exact
    GraphBackend trim/carry/log machinery inherited here — so streamed
    des rounds, cut epochs and :class:`EpochCarry` contents are
    bit-identical to graph/pallas streams fed the same ready rows, not
    merely order-invariant.
    """

    name = "des"
    # GroupStream: dispatch rounds to the numpy mirror, not a jitted
    # program (repro.core.desreplay.stream_program_np)
    stream_numpy = True

    def run(self, cfg: GroupConfig, counts: Dict[int, np.ndarray]
            ) -> Tuple[RunReport, Dict[int, DeliveryLog]]:
        sim_cfg = DESLoopBackend._lower(cfg, counts)
        graph = desgraph_mod.simulate(sim_cfg)
        result = desreplay_mod.replay(graph)
        return _des_report(self.name, cfg, result, graph.groups)

    def run_batch(self, cfgs: List[GroupConfig],
                  counts_list: List[Dict[int, np.ndarray]]
                  ) -> List[Tuple[RunReport, Dict[int, DeliveryLog]]]:
        """Sequential per-point runs (the DES has no batched program);
        overrides the inherited compiled grid so grids stay comparable
        point-for-point with the other backends."""
        return [self.run(c, k) for c, k in zip(cfgs, counts_list)]


# ---------------------------------------------------------------------------
# Streaming execution — per-round message counts on the stacked substrate
# ---------------------------------------------------------------------------


def _trees_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


@dataclasses.dataclass(frozen=True)
class StreamView:
    """Host-side watermark snapshot after one streamed round.

    ``delivered_num[g, m]`` is member position ``m``'s highest delivered
    total-order seq in subgroup ``g``; ``published[g, s]`` sender rank
    ``s``'s total publishes (apps + nulls); ``backlog[g, s]`` its
    window-throttled still-queued app messages.  Padded lanes beyond a
    subgroup's real ``n_members``/``n_senders`` carry garbage — always
    slice with the per-subgroup sizes (as the helpers here do).
    """

    round: int
    delivered_num: np.ndarray            # (G, N_max)
    published: np.ndarray                # (G, S_max)
    backlog: np.ndarray                  # (G, S_max)
    n_members: Tuple[int, ...]
    n_senders: Tuple[int, ...]
    # the round's publish trace (None on a bare GroupStream.view() —
    # only a step() carries what it just published)
    app_pub: Optional[np.ndarray] = None     # (G, S_max)
    nulls: Optional[np.ndarray] = None       # (G, S_max)

    def sender_delivered(self, gid: int) -> np.ndarray:
        """(S_g,) — how many of each sender rank's publishes (apps and
        nulls) EVERY real member of subgroup ``gid`` has delivered: the
        per-sender delivery watermark (seq ``i*S + s`` delivered means
        sender ``s``'s first ``i+1`` publishes are)."""
        n_g, s_g = self.n_members[gid], self.n_senders[gid]
        d = int(self.delivered_num[gid, :n_g].min())
        ranks = np.arange(s_g)
        return np.where(d >= ranks, (d - ranks) // s_g + 1, 0)

    def sender_drained(self, gid: int) -> np.ndarray:
        """(S_g,) bool — sender rank has no queued backlog and every one
        of its publishes so far is delivered at every member of ``gid``
        (the slot-free condition of the serve plane)."""
        s_g = self.n_senders[gid]
        return ((self.backlog[gid, :s_g] == 0)
                & (self.sender_delivered(gid)
                   >= self.published[gid, :s_g]))


class GroupStream:
    """Streaming execution of one :class:`Group` scenario.

    Where :meth:`Group.run` lowers a fixed per-sender message count to a
    schedule upfront, a stream accepts the (G, S_max) app-message counts
    of each round as they happen — the entry point for workloads whose
    send pattern only exists at runtime (the serve plane's decode loop,
    DESIGN.md Sec. 6).  Every :meth:`step` sweeps ALL subgroups as the
    same ONE stacked compiled program (cached per scenario shape in
    :func:`_stream_program`; the first round traces, every later round is
    pure dispatch — a whole session appends exactly one
    :data:`TRACE_EVENTS` entry) and returns the :class:`StreamView`
    watermarks the caller can gate on.  :meth:`finish` drains to
    quiescence and post-processes the accumulated round traces through
    the exact :class:`GraphBackend` machinery scheduled runs use, so the
    resulting :class:`RunReport` and delivery logs are comparable
    like-for-like with ``run``/``run_batch`` (graph and pallas streams
    fed identical rounds are bit-identical)."""

    def __init__(self, group: Group, backend="graph"):
        be = get_backend(backend)
        if not isinstance(be, GraphBackend):
            raise ValueError(
                "streaming runs on the stacked graph/pallas/des "
                f"substrate; got {getattr(be, 'name', backend)!r}")
        cfg = group.cfg
        if not cfg.subgroups:
            raise ValueError("no subgroups")
        self.group = group
        self.backend = be
        # des streams round on the host-side numpy mirror of the same
        # int32 sweep arithmetic (DESIGN.md Sec. 12) — bit-identical
        # rounds, no compiled program
        self._numpy = bool(getattr(be, "stream_numpy", False))
        self._n = tuple(len(s.members) for s in cfg.subgroups)
        self._s = tuple(len(s.senders) for s in cfg.subgroups)
        self._w = tuple(s.window for s in cfg.subgroups)
        self.n_max, self.s_max = max(self._n), max(self._s)
        member_masks, sender_masks = _stack_masks(self._n, self._s)
        if self._numpy:
            self._mask_args: Tuple = () if member_masks is None else (
                np.asarray(member_masks), np.asarray(sender_masks))
            self._program = desreplay_mod.stream_program_np(
                self._w, cfg.flags.null_send)
            self._states = desreplay_mod.batch_states_np(
                self.n_max, self.s_max, len(self._n))
            self._backlogs = np.zeros((len(self._n), self.s_max),
                                      np.int32)
        else:
            self._mask_args = () if member_masks is None else (
                jnp.asarray(member_masks), jnp.asarray(sender_masks))
            self._program = _stream_program(len(self._n), self.n_max,
                                            self.s_max, self._w,
                                            bool(self._mask_args),
                                            cfg.flags.null_send, be.name)
            self._states = sweep_mod.batch_states(self.n_max, self.s_max,
                                                  len(self._n))
            self._backlogs = jnp.zeros((len(self._n), self.s_max),
                                       jnp.int32)
        self._costs = np.stack([_cost_params(cfg, spec)
                                for spec in cfg.subgroups]).astype(
                                    np.float32)
        self._enqueued = [np.zeros(s, np.int64) for s in self._s]
        # virtual-synchrony epoch carry (DESIGN.md Sec. 7): the previous
        # epoch's resend set starts out as this epoch's backlog — the
        # undelivered tail re-publishes ahead of new traffic, per-sender
        # FIFO intact — and counts as enqueued here (it must deliver in
        # THIS view).
        self.carry = group.carry
        self.closed = False
        if self.carry is not None:
            backlogs0 = np.zeros((len(self._n), self.s_max), np.int32)
            for g, resent in enumerate(self.carry.resend):
                backlogs0[g, : len(resent)] = resent
                self._enqueued[g] += resent.astype(np.int64)
            self._backlogs = (backlogs0 if self._numpy
                              else jnp.asarray(backlogs0))
        # running per-sender publish totals, kept host-side so watermark
        # queries (app_publish_index) answer the common "not published
        # yet" case in O(1) instead of re-scanning the round traces
        self._app_cum = np.zeros((len(self._n), self.s_max), np.int64)
        self._pub_cum = np.zeros((len(self._n), self.s_max), np.int64)
        self._batches: List[np.ndarray] = []
        self._app_pub: List[np.ndarray] = []
        self._nulls: List[np.ndarray] = []
        self.rounds = 0
        # device->host transfers of round results (6 a step, 3 a view),
        # and the blocking fetches that carry them (1 a step or view);
        # the numpy mirror (des) makes none
        self.host_syncs = 0
        self.host_waits = 0

    @property
    def shape(self) -> Tuple[int, int]:
        """(G, S_max) — what :meth:`step` expects."""
        return len(self._n), self.s_max

    @property
    def n_members(self) -> Tuple[int, ...]:
        """Per-subgroup real member counts (lanes beyond are padding)."""
        return self._n

    @property
    def n_senders(self) -> Tuple[int, ...]:
        """Per-subgroup real sender counts (lanes beyond are padding)."""
        return self._s

    @property
    def windows(self) -> Tuple[int, ...]:
        """Per-subgroup SMC window (the backpressure bound an admission
        policy throttles against — DESIGN.md Sec. 10)."""
        return self._w

    @property
    def cost_params(self) -> np.ndarray:
        """(G, 6) cost-model coefficients (see :func:`_cost_params`),
        consumable by :func:`fold_cost_np` for host-side time folds."""
        return self._costs.copy()

    def traces(self) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """The accumulated round traces, stacked: ``(batches (G, T, N),
        app_pub (G, T, S), nulls (G, T, S))`` for the T rounds streamed
        so far.  This is the raw material of per-message latency
        reconstruction (delivery watermark per round x per-sender publish
        trace — DESIGN.md Sec. 10); empty T=0 arrays before any step."""
        g, s = self.shape
        if not self.rounds:
            z = np.zeros((g, 0, self.n_max), np.int64)
            return z, np.zeros((g, 0, s), np.int64), \
                np.zeros((g, 0, s), np.int64)
        return (np.stack(self._batches, axis=1),
                np.stack(self._app_pub, axis=1),
                np.stack(self._nulls, axis=1))

    def absorb(self, states, backlogs, batches, app_pub, nulls,
               enqueued) -> None:
        """Install round traces that were executed OUTSIDE this stream —
        inside one fused compiled program that embedded the stream round
        body (:func:`repro.core.sweep.step_backlog` via
        :func:`fused_stream_program`; the fused serve plane,
        DESIGN.md Sec. 6) — as if :meth:`step` had streamed them.

        ``states``/``backlogs`` are the post-run carry (same stacked
        layout :meth:`step` maintains); ``batches``/``app_pub``/``nulls``
        the per-round traces as ``(T, G, ...)`` arrays or length-T lists
        of per-round ``(G, ...)`` rows; ``enqueued`` the per-subgroup
        per-rank app totals the rounds enqueued.  After absorbing,
        :meth:`finish` post-processes through the exact
        :class:`GraphBackend` machinery — a fused run's report and
        delivery logs are the per-round dispatch loop's by construction.
        Only valid on a stream with no rounds streamed yet; an epoch
        CARRY is fine — the wedge-capable fused serve plane absorbs
        each post-cut epoch into the reconfigured stream, whose
        carry-seeded backlog/enqueued state the fused program took as
        its initial operands (``enqueued`` must then count only the
        absorbed rounds' events, which add onto the carry seed)."""
        if self.rounds or self.closed:
            raise RuntimeError("absorb needs a stream with no rounds "
                               "streamed (fresh or carry-seeded)")
        g, s_max = self.shape
        batches = [np.asarray(b, np.int64) for b in batches]
        app_pub = [np.asarray(p, np.int64) for p in app_pub]
        nulls = [np.asarray(x, np.int64) for x in nulls]
        if len(batches) != len(app_pub) or len(batches) != len(nulls):
            raise ValueError("trace lengths disagree")
        for b, p, x in zip(batches, app_pub, nulls):
            if b.shape != (g, self.n_max) or p.shape != (g, s_max) \
                    or x.shape != (g, s_max):
                raise ValueError("trace rows must be (G, N_max)/"
                                 "(G, S_max) shaped")
        if self._numpy:
            self._states = jax.tree_util.tree_map(
                lambda x: np.asarray(x, np.int32), states)
            self._backlogs = np.asarray(backlogs, np.int32)
        else:
            self._states = jax.tree_util.tree_map(jnp.asarray, states)
            self._backlogs = jnp.asarray(backlogs, jnp.int32)
        self._batches, self._app_pub, self._nulls = batches, app_pub, \
            nulls
        for p, x in zip(app_pub, nulls):
            self._app_cum += p
            self._pub_cum += p + x
        for gid, s_g in enumerate(self._s):
            self._enqueued[gid] += np.asarray(enqueued[gid],
                                              np.int64)[:s_g]
        self.rounds = len(batches)

    def step(self, ready) -> StreamView:
        """One protocol round: ``ready[g, s]`` app messages become ready
        at sender rank ``s`` of subgroup ``g`` (padded lanes must be 0).
        Window-throttled messages are carried in the backlog, exactly as
        the scheduled scan does.

        In a ``jax.profiler`` trace a round is the host span
        ``spindle.stream.step`` (metadata ``round``: the 0-based round
        index) with two child spans: ``spindle.stream.dispatch``
        (launching the compiled round program; on a ``des`` stream, the
        numpy round itself) and one ``spindle.stream.readback`` (the
        round's ``batch``/``app_pub``/``nulls`` and the three watermarks
        of :meth:`view` brought to the host in one blocking fetch, which
        also waits for the program to finish).  The step span's own time
        outside those is host bookkeeping.  A step adds 6 to
        ``host_syncs`` (arrays transferred) and 1 to ``host_waits``
        (blocking fetches); a ``des`` stream adds to neither."""
        with TraceAnnotation("spindle.stream.step", round=self.rounds):
            return self._step(ready)

    def _step(self, ready) -> StreamView:
        if self.closed:
            raise RuntimeError(
                "stream closed by a view change; continue on the stream "
                "reconfigure() returned")
        ready = np.asarray(ready, np.int32)
        if ready.shape != self.shape:
            raise ValueError(f"ready must be {self.shape}, got "
                             f"{ready.shape}")
        for g, s_g in enumerate(self._s):
            if ready[g, s_g:].any():
                raise ValueError(
                    f"subgroup {g} has {s_g} senders but ready names "
                    f"padded lanes {np.nonzero(ready[g, s_g:])[0] + s_g}")
            self._enqueued[g] += ready[g, :s_g].astype(np.int64)
        with TraceAnnotation("spindle.stream.dispatch"):
            (self._states, self._backlogs), (batch, pub, nulls) = \
                self._program(self._states, self._backlogs,
                              ready if self._numpy else jnp.asarray(ready),
                              *self._mask_args)
        batch, pub, nulls, delivered_num, published, backlog = \
            self._fetch(batch, pub, nulls, self._states.delivered_num,
                        self._states.published, self._backlogs)
        self._batches.append(batch)
        self._app_pub.append(pub)
        self._nulls.append(nulls)
        self._app_cum += pub
        self._pub_cum += pub + nulls
        self.rounds += 1
        return self._view(delivered_num, published, backlog,
                          app_pub=pub, nulls=nulls)

    def view(self) -> StreamView:
        """The current watermarks (``delivered_num``, ``published``,
        ``backlog``), read back to the host in one blocking fetch: one
        ``spindle.stream.readback`` span, one ``host_waits``, three
        ``host_syncs``."""
        return self._view(*self._fetch(self._states.delivered_num,
                                       self._states.published,
                                       self._backlogs))

    def _fetch(self, *arrays) -> Tuple[np.ndarray, ...]:
        """``arrays`` on the host, in one ``spindle.stream.readback``
        span.  ``jax.device_get`` starts every device->host copy before
        it waits on any, so their latencies overlap and the host blocks
        once; numpy arrays (the des mirror, a wrapped program's outputs)
        pass through unchanged."""
        with TraceAnnotation("spindle.stream.readback"):
            if not self._numpy:
                self.host_syncs += len(arrays)
                self.host_waits += 1
            return jax.device_get(arrays)

    def _view(self, delivered_num, published, backlog, app_pub=None,
              nulls=None) -> StreamView:
        return StreamView(
            round=self.rounds, delivered_num=delivered_num,
            published=published, backlog=backlog,
            n_members=self._n, n_senders=self._s, app_pub=app_pub,
            nulls=nulls)

    def app_publish_index(self, gid: int, rank: int,
                          k: int) -> Optional[int]:
        """Publish index (0-based, counting apps AND nulls) of sender
        ``rank``'s ``k``-th app publish (1-based) in subgroup ``gid``,
        from the accumulated round traces — or None if fewer than ``k``
        apps have been published yet.  The serve fan-out pins its
        slot-release watermarks on this (apps precede nulls within a
        round, matching the sweep's ``published + app_pub + nulls``).

        The common "still window-throttled" answer is O(1) (running
        totals); the trace scan runs only once a hold's k-th app has
        actually published — once per query target, not per round."""
        if k <= 0 or self._app_cum[gid, rank] < k:
            return None
        apps = np.asarray([r[gid, rank] for r in self._app_pub], np.int64)
        nulls = np.asarray([r[gid, rank] for r in self._nulls], np.int64)
        app_cum = np.cumsum(apps)
        r = int(np.searchsorted(app_cum, k))
        pub_before = int(np.cumsum(apps + nulls)[r] - apps[r] - nulls[r])
        return pub_before + int(k - (app_cum[r] - apps[r])) - 1

    def quiescent(self, view: Optional[StreamView] = None) -> bool:
        """No backlog anywhere and every PUBLISHED message delivered by
        every real member.

        Stricter than "the round-robin prefix is delivered": a sender
        whose last window-throttled app publishes just as delivery
        catches up sits beyond the rr prefix for a round or two until
        the null-send scheme covers the lagging ranks — the prefix test
        would call that quiescent and strand the message (the
        virtual-synchrony resend tests caught exactly this timing).
        With null-send on, an undelivered published message always makes
        progress, so requiring ``delivered >= every sender's last
        published seq`` still terminates; with null-send off it may
        never hold, which the :meth:`finish` fixed-point exit handles."""
        v = self.view() if view is None else view
        for g, (n_g, s_g) in enumerate(zip(self._n, self._s)):
            if v.backlog[g, :s_g].any():
                return False
            counts = v.published[g, :s_g].astype(np.int64)
            if not counts.any():
                continue
            ranks = np.arange(s_g)
            last_seq = (counts - 1) * s_g + ranks
            need = int(last_seq[counts > 0].max())
            if (v.delivered_num[g, :n_g] < need).any():
                return False
        return True

    def finish(self, settle_max: Optional[int] = None
               ) -> Tuple[RunReport, Dict[int, DeliveryLog]]:
        """Drain with zero-ready rounds until quiescent, then reconstruct
        delivery logs and the unified report from the accumulated traces.
        Also installs the logs on the owning Group and fires its delivery
        upcalls, mirroring :meth:`Group.run`.

        The drain is not a fixed budget: a window-throttled backlog of B
        messages needs ~3·B/window rounds, so the loop instead runs until
        quiescence or a protocol FIXED POINT (a zero-ready round that
        changes nothing can never be followed by one that does — every
        predicate is monotone in the state).  The fixed-point exit covers
        scenarios that can never quiesce, e.g. ``null_send=False`` with
        uneven sender counts.  ``settle_max`` optionally caps the drain
        (the capped-off remainder reports as ``stalled``)."""
        if self.closed:
            raise RuntimeError(
                "stream closed by a view change; finish the stream "
                "reconfigure() returned")
        zeros = np.zeros(self.shape, np.int32)
        settled = 0
        while not self.quiescent():
            if settle_max is not None and settled >= settle_max:
                break
            prev_states, prev_backlogs = self._states, self._backlogs
            self.step(zeros)
            settled += 1
            if settle_max is None and _trees_equal(
                    (prev_states, prev_backlogs),
                    (self._states, self._backlogs)):
                break                        # fixed point: done evolving
        agg = self._aggregate()
        if self.rounds and np.asarray(self._backlogs).any():
            agg.stalled = True                # gave up with work queued
        report = self.backend._report(agg)
        report.extras["streamed_rounds"] = self.rounds
        self.group.delivery_logs = agg.logs
        self.group.last_report = report
        self.group._fire_upcalls()
        return report, agg.logs

    def _aggregate(self, app_pub=None, nulls=None) -> _GraphAgg:
        """Run the accumulated round traces through the exact
        :class:`GraphBackend` post-processing a scheduled run uses.
        ``app_pub``/``nulls`` accept already-stacked (G, T, S) traces so
        the cut path, which needs them for the stable-apps computation
        anyway, does not stack them twice."""
        agg = _GraphAgg()
        if self.rounds:
            batches = np.stack(self._batches, axis=1)       # (G, T, N)
            if app_pub is None:
                app_pub = np.stack(self._app_pub, axis=1)   # (G, T, S)
            if nulls is None:
                nulls = np.stack(self._nulls, axis=1)
            round_t, round_w = _fold_cost_stacked(
                jnp.asarray(app_pub), jnp.asarray(self._costs))
            outs = [batches, app_pub, nulls,
                    np.asarray(round_t), np.asarray(round_w)]
            counts = {g: self._enqueued[g] for g in range(len(self._s))}
            self.backend._finalize(self.group.cfg, counts, outs,
                                   (self.rounds,) * len(self._n), agg)
        return agg

    # -- the virtual-synchrony cut (view changes mid-stream) -----------------

    def reconfigure(self, view: "views_mod.View") -> "GroupStream":
        """Close this epoch at the virtual-synchrony cut and hand its
        in-flight state to a new stream for ``view`` (DESIGN.md Sec. 7).

        Wedge semantics: no settle rounds run — the cut is taken from the
        SST watermarks exactly as they stand, like a real wedge that
        cannot wait out a failed node.  Per subgroup the ragged trim is
        the highest seq received by every SURVIVING member
        (:func:`repro.core.sst.ragged_trim`); every surviving member's
        delivery advances exactly TO the trim, so the closing epoch's
        log is identical at every survivor (*everywhere* — and nobody
        rolls back, because a member's delivered watermark is a min over
        its stale view of the same monotone column), while everything
        beyond the trim is delivered *nowhere*.  Undelivered app
        messages of surviving senders — published-but-unstable plus the
        window-throttled backlog — become the new stream's initial
        backlog: the FIFO tail, resent in the new view.  A failed
        sender's unstable messages die with it.

        The closing epoch's cut-clipped logs and report are installed on
        the owning Group and its upcalls fire, mirroring :meth:`finish`
        (the report carries ``extras["view_change"]``).  The returned
        stream belongs to ``self.group.reconfigure(view)`` and carries
        an :class:`EpochCarry`; when the padded stack shape survives the
        change it keeps dispatching the SAME cached one-round program —
        a view change is a watermark hand-off, not a fresh-epoch
        restart."""
        if self.closed:
            raise RuntimeError("stream already closed by a view change")
        cfg = self.group.cfg
        alive = set(view.members)
        new_group = self.group.reconfigure(view)
        gid_map, sender_maps = new_group._gid_map, new_group._sender_maps
        received = np.asarray(self._states.received_num)    # (G, N_max)
        t = self.rounds
        app_pub = (np.stack(self._app_pub, axis=1) if t else
                   np.zeros((len(self._n), 0, self.s_max), np.int64))
        nulls = (np.stack(self._nulls, axis=1) if t else
                 np.zeros((len(self._n), 0, self.s_max), np.int64))
        cut_seqs: Dict[int, int] = {}
        stable: Dict[int, np.ndarray] = {}
        for gid, spec in enumerate(cfg.subgroups):
            n_g, s_g = self._n[gid], self._s[gid]
            alive_pos = np.asarray([m in alive for m in spec.members])
            cut = sst.ragged_trim(received[gid, :n_g], alive_pos)
            pubs_at_cut = sst.sender_counts(np.asarray(cut + 1), s_g)
            stable[gid] = np.asarray(
                [delivery_mod.apps_in_publish_prefix(
                    app_pub[gid, :, s], nulls[gid, :, s],
                    int(pubs_at_cut[s])) for s in range(s_g)], np.int64)
            cut_seqs[gid] = cut
        resend_t, stable_t, base_t, cut_t = [], [], [], []
        for old_gid in sorted(gid_map):
            new_gid = gid_map[old_gid]
            s_new = len(new_group.cfg.subgroups[new_gid].senders)
            resend = np.zeros(s_new, np.int64)
            stb = np.zeros(s_new, np.int64)
            base = np.zeros(s_new, np.int64)
            for old_rank, new_rank in sender_maps[old_gid]:
                stb[new_rank] = stable[old_gid][old_rank]
                resend[new_rank] = (self._enqueued[old_gid][old_rank]
                                    - stb[new_rank])
                prev = (int(self.carry.app_base[old_gid][old_rank])
                        if self.carry is not None else 0)
                base[new_rank] = prev + stb[new_rank]
            resend_t.append(resend)
            stable_t.append(stb)
            base_t.append(base)
            cut_t.append(cut_seqs[old_gid])
        new_group.carry = EpochCarry(
            from_epoch=cfg.epoch, cut_seq=tuple(cut_t),
            resend=tuple(resend_t), stable_apps=tuple(stable_t),
            app_base=tuple(base_t))
        self._close_at_cut(cut_seqs, alive, new_group.carry,
                           app_pub, nulls, stable)
        return new_group.stream(backend=self.backend.name)

    def _close_at_cut(self, cut_seqs: Dict[int, int], alive,
                      carry: EpochCarry, app_pub, nulls,
                      stable_by_old_rank: Dict[int, np.ndarray]) -> None:
        """Finalize the closing epoch's logs/report with every surviving
        member's delivery advanced to the ragged trim."""
        cfg = self.group.cfg
        agg = self._aggregate(app_pub, nulls)
        for gid, spec in enumerate(cfg.subgroups):
            log = agg.logs.get(gid)
            if log is None:
                continue
            for node in spec.members:
                if node in alive:
                    log.delivered_seq[node] = cut_seqs[gid]
        # re-derive the log-dependent accounting after the cut advance
        # (the in-protocol numbers were computed from the pre-wedge
        # watermarks; latency samples keep their in-protocol rounds —
        # cut-advanced deliveries have no delivery round to sample)
        agg.delivered_app = agg.delivered_null = 0
        agg.per_node_bytes = {}
        for gid, spec in enumerate(cfg.subgroups):
            log = agg.logs.get(gid)
            if log is None:
                continue
            for node in spec.members:
                n_app, n_null = log.app_null_counts(node)
                agg.delivered_app += n_app
                agg.delivered_null += n_null
                agg.per_node_bytes[node] = \
                    agg.per_node_bytes.get(node, 0.0) + \
                    n_app * spec.msg_size
        report = self.backend._report(agg)
        report.extras["streamed_rounds"] = self.rounds
        report.extras["view_change"] = {
            "cut_seq": {g: int(c) for g, c in cut_seqs.items()},
            "resend_msgs": carry.total_resend(),
            # Stable app counts in the OLD view's rank space (the carry's
            # stable_apps are remapped to the new view and drop failed
            # senders): a failed sender's stable prefix is only visible
            # here.  The serve plane reads it to account a dead slot's
            # delivered apps; gradsync reads it to cap a dead
            # contributor's deliverable watermark.
            "stable_apps_by_old_rank": {
                g: s.copy() for g, s in stable_by_old_rank.items()},
        }
        self.group.delivery_logs = agg.logs
        self.group.last_report = report
        self.group._fire_upcalls()
        self.closed = True


def _sum_delivered(logs: Mapping[int, DeliveryLog]) -> Tuple[int, int]:
    a = n = 0
    for log in logs.values():
        for node in log.delivered_seq:
            da, dn = log.app_null_counts(node)
            a, n = a + da, n + dn
    return a, n


register_backend("des", DESBackend)
register_backend("des-loop", DESLoopBackend)
register_backend("graph", GraphBackend)
register_backend("pallas", PallasBackend)
