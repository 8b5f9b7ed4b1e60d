"""Two-phase DES scale-out (DESIGN.md Sec. 12).

Fast tier: seeded property tests that the two-phase ``des`` backend
(phase 1 :mod:`repro.core.desgraph` + phase 2
:mod:`repro.core.desreplay`) is bit-identical to the legacy ``des-loop``
— reports, delivery logs, latency percentiles, cost extras — across
heterogeneous stacked subgroups, null-send on/off and the full flag
lattice corners; graph-vs-des conformance at N ∈ {256, 1024}; the
deterministic ``(time, node, seq)`` event tie-break under permuted
subgroup declaration order; and the vectorized egress-link chain vs a
reference sequential loop.

Soak tier (``-m soak``): the N=4096 fleet — two-phase des against the
stacked graph program on the same schedule.
"""

import dataclasses

import numpy as np
import pytest

from repro import api
from repro.core import desgraph, desreplay
from repro.core import group as group_mod
from repro.core import simulator as sim

fast = pytest.mark.fast
soak = pytest.mark.soak


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _eq(a, b, path=""):
    """Bit-exact structural equality (NaN == NaN, numpy vs scalar)."""
    if isinstance(a, np.ndarray) or isinstance(b, np.ndarray):
        assert np.array_equal(np.asarray(a), np.asarray(b),
                              equal_nan=True), path
    elif isinstance(a, dict):
        assert set(a) == set(b), path
        for k in a:
            if k == "backend":
                continue
            _eq(a[k], b[k], f"{path}.{k}")
    elif isinstance(a, (list, tuple)):
        assert len(a) == len(b), path
        for i, (x, y) in enumerate(zip(a, b)):
            _eq(x, y, f"{path}[{i}]")
    elif isinstance(a, float) and isinstance(b, float) \
            and np.isnan(a) and np.isnan(b):
        pass
    else:
        assert a == b, (path, a, b)


def _run(cfg, backend):
    g = api.Group(cfg)
    report = g.run(backend=backend)
    return report, g.delivery_logs


def _assert_identical(cfg, ctx=""):
    """des (two-phase) == des-loop (legacy), bit for bit."""
    r1, l1 = _run(cfg, "des-loop")
    r2, l2 = _run(cfg, "des")
    _eq(dataclasses.asdict(r1), dataclasses.asdict(r2), f"{ctx}:report")
    assert set(l1) == set(l2), ctx
    for gid in l1:
        _eq(vars(l1[gid]), vars(l2[gid]), f"{ctx}:log{gid}")


def _rand_stack(rng, n_nodes, n_groups):
    """A random heterogeneous stacked-subgroup scenario."""
    nodes = np.arange(n_nodes)
    specs = []
    for _ in range(n_groups):
        n_m = int(rng.integers(2, min(n_nodes, 7) + 1))
        members = tuple(int(m) for m in
                        rng.choice(nodes, size=n_m, replace=False))
        n_s = int(rng.integers(1, n_m + 1))
        senders = tuple(int(s) for s in
                        rng.choice(members, size=n_s, replace=False))
        specs.append(api.SubgroupSpec(
            members=members, senders=senders,
            window=int(rng.integers(2, 7)),
            msg_size=int(rng.choice([64, 512, 4096])),
            n_messages=int(rng.integers(1, 9))))
    return api.GroupConfig(members=tuple(range(n_nodes)),
                           subgroups=tuple(specs))


def _big_cfg(n_nodes, n_senders=8, n_messages=4, window=16,
             rounds=None):
    spec = api.SubgroupSpec(members=tuple(range(n_nodes)),
                            senders=tuple(range(n_senders)),
                            window=window, msg_size=1024,
                            n_messages=n_messages)
    return api.GroupConfig(members=tuple(range(n_nodes)),
                           subgroups=(spec,), rounds=rounds)


def _digest(logs):
    """Order-sensitive per-member delivery digest for graph-vs-des
    conformance: the delivered sequence of (rank, idx, is_app)."""
    out = {}
    for gid, log in sorted(logs.items()):
        for node in sorted(log.delivered_seq):
            out[(gid, node)] = log.sequence(node)
    return out


# ---------------------------------------------------------------------------
# des2 == des-loop, bit-identical (fast)
# ---------------------------------------------------------------------------

@fast
def test_two_phase_identical_heterogeneous_stacks():
    rng = np.random.default_rng(1234)
    for case in range(8):
        cfg = _rand_stack(rng, n_nodes=int(rng.integers(4, 9)),
                          n_groups=int(rng.integers(1, 4)))
        _assert_identical(cfg, ctx=f"case{case}")


@fast
def test_two_phase_identical_null_send_on_off():
    rng = np.random.default_rng(77)
    for case in range(4):
        base = _rand_stack(rng, n_nodes=6, n_groups=2)
        for null_send in (True, False):
            cfg = dataclasses.replace(
                base, flags=dataclasses.replace(base.flags,
                                                null_send=null_send))
            _assert_identical(cfg, ctx=f"case{case}:null={null_send}")


@fast
def test_two_phase_identical_flag_corners():
    base = _rand_stack(np.random.default_rng(9), n_nodes=7, n_groups=3)
    corners = [
        api.SpindleFlags(batch_receive=False, batch_delivery=False,
                         batch_send=False, null_send=False,
                         early_lock_release=False, batched_upcall=False,
                         wait_stability=False),
        dataclasses.replace(api.SpindleFlags(), memcpy_delivery=True,
                            memcpy_send=True, disk_append=True),
        dataclasses.replace(api.SpindleFlags(),
                            early_lock_release=False),
        dataclasses.replace(api.SpindleFlags(), batch_send=False,
                            wait_stability=False),
    ]
    for i, flags in enumerate(corners):
        _assert_identical(dataclasses.replace(base, flags=flags),
                          ctx=f"corner{i}")


@fast
def test_two_phase_identical_n64():
    _assert_identical(_big_cfg(64, n_messages=6), ctx="n64")


# ---------------------------------------------------------------------------
# graph-vs-des conformance at fleet scale (fast: 256 and 1024)
# ---------------------------------------------------------------------------

def _conformance(n_nodes, rounds, n_messages, n_senders=8):
    cfg = _big_cfg(n_nodes, n_senders=n_senders, n_messages=n_messages,
                   rounds=rounds)
    r_des, l_des = _run(cfg, "des")
    r_g, l_g = _run(cfg, "graph")
    assert not r_des.stalled and not r_g.stalled
    assert r_des.delivered_app_msgs == r_g.delivered_app_msgs
    assert _digest(l_des) == _digest(l_g)


@fast
def test_graph_vs_des_conformance_n256():
    _conformance(256, rounds=24, n_messages=4)


@fast
def test_graph_vs_des_conformance_n1024():
    _conformance(1024, rounds=16, n_messages=2)


@soak
def test_graph_vs_des_conformance_n4096():
    _conformance(4096, rounds=24, n_messages=2, n_senders=2)


# ---------------------------------------------------------------------------
# deterministic event tie-breaking (the (time, node, seq) heap key)
# ---------------------------------------------------------------------------

@fast
def test_event_graph_invariant_under_subgroup_permutation():
    """Permuting the declaration order of disjoint subgroups must not
    reorder same-timestamp events: the per-subgroup slices of the event
    graph are unchanged (the explicit ``(time, node, seq)`` key breaks
    ties by node, never by arrival order of heap pushes)."""
    sa = api.SubgroupSpec(members=(0, 1, 2), senders=(0, 1),
                          window=3, msg_size=512, n_messages=6)
    sb = api.SubgroupSpec(members=(3, 4, 5, 6), senders=(3, 5, 6),
                          window=4, msg_size=256, n_messages=5)
    members = tuple(range(7))
    cfg_ab = api.GroupConfig(members=members, subgroups=(sa, sb))
    cfg_ba = api.GroupConfig(members=members, subgroups=(sb, sa))
    graphs = {}
    for tag, cfg in (("ab", cfg_ab), ("ba", cfg_ba)):
        counts = {g: np.full(len(s.senders), s.n_messages, np.int64)
                  for g, s in enumerate(cfg.subgroups)}
        graphs[tag] = desgraph.simulate(
            group_mod.DESLoopBackend._lower(cfg, counts))
    ga, gb = graphs["ab"], graphs["ba"]
    # the global sweep timeline is identical (gids don't enter the key)
    _eq(ga.sweep_node, gb.sweep_node, "sweep_node")
    _eq(ga.sweep_time, gb.sweep_time, "sweep_time")
    _eq(ga.sweep_dur, gb.sweep_dur, "sweep_dur")
    # per-subgroup event slices match under the gid permutation
    perm = {0: 1, 1: 0}                   # ab gid -> ba gid
    for key in ("deliv", "pub"):
        gid_a = getattr(ga, f"{key}_gid")
        gid_b = getattr(gb, f"{key}_gid")
        for g_a, g_b in perm.items():
            ma, mb = gid_a == g_a, gid_b == g_b
            fields = {"deliv": ("member", "lo", "hi", "napp", "time"),
                      "pub": ("rank", "count", "is_null", "time")}[key]
            for f in fields:
                _eq(getattr(ga, f"{key}_{f}")[ma],
                    getattr(gb, f"{key}_{f}")[mb],
                    f"{key}_{f}:g{g_a}")


@fast
def test_two_phase_identical_under_subgroup_permutation():
    """End to end: the permuted-declaration scenario still replays
    bit-identically to the legacy loop (per-subgroup logs match under
    the gid relabeling)."""
    sa = api.SubgroupSpec(members=(0, 1, 2), senders=(0, 1),
                          window=3, msg_size=512, n_messages=6)
    sb = api.SubgroupSpec(members=(3, 4, 5, 6), senders=(3, 5, 6),
                          window=4, msg_size=256, n_messages=5)
    members = tuple(range(7))
    _assert_identical(api.GroupConfig(members=members,
                                      subgroups=(sa, sb)), "ab")
    _assert_identical(api.GroupConfig(members=members,
                                      subgroups=(sb, sa)), "ba")


# ---------------------------------------------------------------------------
# the vectorized egress-link chain (phase 1's only float refactor)
# ---------------------------------------------------------------------------

@fast
def test_post_chain_matches_sequential_reference():
    """The two cumsum regimes of ``Phase1._post_record`` reproduce the
    sequential ``L_i = fl(max(L_{i-1}, t_i) + ser)`` recurrence bit for
    bit, for serialization both above and below the post cost."""
    rng = np.random.default_rng(3)
    cfg = api.single_group(5, n_senders=2, n_messages=1)
    counts = {0: np.ones(2, np.int64)}
    for size in (64, 700, 4096, 65536):
        for link0_off in (-3.0, 0.0, 2.5, 1000.0):
            p1 = desgraph.Phase1(
                group_mod.DESLoopBackend._lower(cfg, counts))
            net = p1.cfg.net
            t0 = float(rng.uniform(5.0, 50.0))
            src = 0
            p1.link_free[src] = t0 + link0_off
            link0 = p1.link_free[src]
            g = p1.groups[0]
            st = p1._stream_for(g, 0, src)
            n = len(st.dsts)
            # reference: the legacy sequential chain
            ser = net.serialization(size)
            ref, link, t = [], link0, t0
            for _ in range(n):
                t += net.post_us
                link = max(link, t) + ser
                ref.append(link)
            p1._post_record(src, t0, st, size, 7, g.recv_seen, 0)
            wl = net.wire_latency(min(size, 4096))
            got = np.asarray(st.arrs[-1])
            expect = np.maximum(np.asarray(ref) + wl, 0.0)
            np.testing.assert_array_equal(got, expect)
            assert p1.link_free[src] == ref[-1]


# ---------------------------------------------------------------------------
# the des stream mirror (sweep arithmetic host-side)
# ---------------------------------------------------------------------------

@fast
def test_numpy_sweep_mirror_matches_jax_rounds():
    """:func:`repro.core.desreplay.sweep_np` steps produce the same
    int32 state trajectory as the compiled stream program."""
    rng = np.random.default_rng(21)
    s1 = api.SubgroupSpec(members=(0, 1, 2, 3), senders=(0, 2),
                          window=3, msg_size=512, n_messages=10)
    s2 = api.SubgroupSpec(members=(2, 3, 4, 5, 6), senders=(3, 4, 5, 6),
                          window=5, msg_size=128, n_messages=10)
    cfg = api.GroupConfig(members=tuple(range(7)), subgroups=(s1, s2))
    streams = {be: api.Group(cfg).stream(backend=be)
               for be in ("graph", "des")}
    assert streams["des"]._numpy and not streams["graph"]._numpy
    for _ in range(10):
        ready = rng.integers(0, 3, size=(2, 4)).astype(np.int32)
        ready[0, 2:] = 0
        va = streams["graph"].step(ready.copy())
        vb = streams["des"].step(ready.copy())
        _eq(np.asarray(va.delivered_num), np.asarray(vb.delivered_num))
        _eq(np.asarray(va.published), np.asarray(vb.published))
        _eq(np.asarray(va.backlog), np.asarray(vb.backlog))
        _eq(np.asarray(va.app_pub), np.asarray(vb.app_pub))
        _eq(np.asarray(va.nulls), np.asarray(vb.nulls))
    ra, la = streams["graph"].finish()
    rb, lb = streams["des"].finish()
    _eq(dataclasses.asdict(ra), dataclasses.asdict(rb), "report")
    _eq({k: vars(v) for k, v in la.items()},
        {k: vars(v) for k, v in lb.items()}, "logs")


@fast
def test_des_loop_backend_still_runs_and_rejects_streaming():
    cfg = api.single_group(3, n_senders=2, n_messages=4)
    report = api.Group(cfg).run(backend="des-loop")
    assert report.backend == "des-loop"
    assert report.delivered_app_msgs == 2 * 4 * 3
    with pytest.raises(ValueError, match="graph/pallas"):
        api.Group(cfg).stream(backend="des-loop")


@fast
def test_des_batch_runs_sequentially_per_point():
    """DESBackend.run_batch must bypass the inherited compiled grid."""
    cfg = api.single_group(3, n_senders=2, n_messages=3)
    g = api.Group(cfg)
    sizes = [64, 1024]
    cfgs = [dataclasses.replace(
        cfg, subgroups=(dataclasses.replace(cfg.subgroups[0],
                                            msg_size=s),))
        for s in sizes]
    reports = [api.Group(c).run(backend="des") for c in cfgs]
    loop = [api.Group(c).run(backend="des-loop") for c in cfgs]
    for r2, r1 in zip(reports, loop):
        _eq(dataclasses.asdict(r1), dataclasses.asdict(r2))
