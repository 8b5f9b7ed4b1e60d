"""Stacked multi-subgroup execution: the whole group as ONE device-sharded
compiled program.

Covers, bottom-up:

* masked round-robin arithmetic (``sst.rr_prefix_masked`` /
  ``sender_counts_masked``) equals the unmasked forms on full masks and
  the unpadded forms on padded inputs;
* the masked padded sweep is bit-identical to the unpadded sweep on the
  active sub-array (seeded property test — hypothesis is not installed);
* a G>=8-subgroup scenario runs as ONE compiled program (a single
  TRACE_EVENTS entry) with delivery logs bit-identical to sequential
  per-subgroup runs on graph and pallas;
* ``run_batch`` shape-mismatch errors name the offending grid point;
* the placement policy degrades to vmap on one device and shards over
  virtual CPU devices (subprocess with XLA_FLAGS, not in the fast gate)
  with bit-identical results.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import group as group_mod
from repro.core import placement, sst
from repro.core import sweep as sweep_mod

fast = pytest.mark.fast


# ---------------------------------------------------------------------------
# masked round-robin arithmetic
# ---------------------------------------------------------------------------

@fast
def test_rr_prefix_masked_equals_unmasked_on_full_mask():
    rng = np.random.default_rng(1)
    for _ in range(20):
        s = int(rng.integers(1, 9))
        counts = jnp.asarray(rng.integers(0, 6, size=(3, s)), jnp.int32)
        mask = jnp.ones(s, bool)
        got = np.asarray(sst.rr_prefix_masked(counts, mask, s))
        want = np.asarray(sst.rr_prefix(counts))
        np.testing.assert_array_equal(got, want)


@fast
def test_rr_prefix_masked_ignores_padded_suffix():
    rng = np.random.default_rng(2)
    for _ in range(20):
        s = int(rng.integers(1, 6))
        pad = int(rng.integers(1, 5))
        counts = rng.integers(0, 6, size=s)
        padded = np.concatenate(
            [counts, rng.integers(0, 9, size=pad)])       # garbage suffix
        mask = np.arange(s + pad) < s
        got = int(sst.rr_prefix_masked(jnp.asarray(padded, jnp.int32),
                                       jnp.asarray(mask), s))
        want = int(sst.rr_prefix(counts))
        assert got == want, (counts, padded)


@fast
def test_sender_counts_masked_matches_unmasked_prefix():
    rng = np.random.default_rng(3)
    for _ in range(20):
        s = int(rng.integers(1, 6))
        pad = int(rng.integers(0, 4))
        prefix = jnp.asarray(rng.integers(0, 30, size=4), jnp.int32)
        got = np.asarray(sst.sender_counts_masked(prefix, s, s + pad))
        want = np.asarray(sst.sender_counts(prefix, s))
        np.testing.assert_array_equal(got[..., :s], want)


# ---------------------------------------------------------------------------
# masked padded sweep == unpadded sweep (the stacking correctness core)
# ---------------------------------------------------------------------------

def _random_scenario(rng):
    n = int(rng.integers(1, 6))
    s = int(rng.integers(1, n + 1))
    rounds = int(rng.integers(4, 20))
    window = int(rng.choice([2, 4, 8, 1 << 20]))
    sched = rng.integers(0, 3, size=(rounds, s)).astype(np.int32)
    null_send = bool(rng.integers(0, 2))
    return n, s, window, sched, null_send


@fast
def test_masked_padded_scan_matches_unpadded_scan():
    """Pad members and senders with garbage-free suffix slots: the active
    sub-array of every per-round trace must be bit-identical to the
    unpadded scan, and padded sender lanes must never publish."""
    rng = np.random.default_rng(20260730)
    for case in range(25):
        n, s, window, sched, null_send = _random_scenario(rng)
        n_pad = n + int(rng.integers(0, 4))
        s_pad = s + int(rng.integers(0, 4))
        s_pad = min(s_pad, n_pad)              # senders are members
        state = sweep_mod.SweepState.init(n, s)
        _, (batches, app_pub, nulls) = sweep_mod.scan_rounds(
            state, jnp.asarray(sched), window=window, null_send=null_send)
        padded_sched = np.zeros((sched.shape[0], s_pad), np.int32)
        padded_sched[:, :s] = sched
        pstate = sweep_mod.SweepState.init(n_pad, s_pad)
        member_mask = np.arange(n_pad) < n
        sender_mask = np.arange(s_pad) < s
        _, (pbatches, papp, pnulls) = sweep_mod.scan_rounds(
            pstate, jnp.asarray(padded_sched), window=window,
            null_send=null_send, member_mask=jnp.asarray(member_mask),
            sender_mask=jnp.asarray(sender_mask))
        np.testing.assert_array_equal(np.asarray(pbatches)[:, :n],
                                      np.asarray(batches), err_msg=f"case {case}")
        np.testing.assert_array_equal(np.asarray(papp)[:, :s],
                                      np.asarray(app_pub), err_msg=f"case {case}")
        np.testing.assert_array_equal(np.asarray(pnulls)[:, :s],
                                      np.asarray(nulls), err_msg=f"case {case}")
        assert not np.asarray(papp)[:, s:].any(), f"case {case}: padded sender published"
        assert not np.asarray(pnulls)[:, s:].any(), f"case {case}: padded sender sent nulls"


# ---------------------------------------------------------------------------
# G>=8 subgroups: ONE compiled program, bit-identical to sequential runs
# ---------------------------------------------------------------------------

def _hetero_group(n_sub=8, seed=42):
    rng = np.random.default_rng(seed)
    specs = []
    for _ in range(n_sub):
        n = int(rng.integers(2, 6))
        s = int(rng.integers(1, n + 1))
        specs.append(api.SubgroupSpec(
            members=tuple(range(n)), senders=tuple(range(s)),
            msg_size=int(rng.choice([256, 1024])),
            window=int(rng.choice([4, 8, 16])),
            n_messages=int(rng.integers(3, 12))))
    n_nodes = max(len(sp.members) for sp in specs)
    return api.GroupConfig(members=tuple(range(n_nodes)),
                           subgroups=tuple(specs))


@fast
@pytest.mark.parametrize("backend", ["graph", "pallas"])
def test_eight_subgroups_single_trace_bit_identical(backend):
    cfg = _hetero_group()
    g_warm = api.Group(cfg)
    g_warm.run(backend=backend)                # cold: traces (<= once)
    before = len(group_mod.TRACE_EVENTS)
    g = api.Group(cfg)
    r = g.run(backend=backend)
    assert len(group_mod.TRACE_EVENTS) == before, \
        "warm 8-subgroup run re-dispatched/re-traced"
    assert not r.stalled
    for gid, spec in enumerate(cfg.subgroups):
        solo = api.GroupConfig(members=spec.members, subgroups=(spec,),
                               flags=cfg.flags)
        gi = api.Group(solo)
        gi.run(backend=backend)
        stacked, alone = g.delivery_logs[gid], gi.delivery_logs[0]
        assert stacked.delivered_seq == alone.delivered_seq, (backend, gid)
        assert len(stacked.is_app) == len(alone.is_app)
        for x, y in zip(stacked.is_app, alone.is_app):
            np.testing.assert_array_equal(x, y, err_msg=f"{backend} {gid}")


@fast
def test_eight_subgroup_cold_run_is_one_trace():
    # a window no other test uses -> a fresh cache key, one trace exactly
    cfg = _hetero_group(seed=97)
    sub = tuple(dataclasses.replace(s, window=19) for s in cfg.subgroups)
    cfg = dataclasses.replace(cfg, subgroups=sub)
    before = len(group_mod.TRACE_EVENTS)
    api.Group(cfg).run(backend="graph")
    assert len(group_mod.TRACE_EVENTS) == before + 1, \
        "8 subgroups did not compile as ONE program"


# ---------------------------------------------------------------------------
# run_batch: named grid-point shape errors + placement policy
# ---------------------------------------------------------------------------

@fast
def test_run_batch_shape_mismatch_names_grid_point():
    spec = api.SubgroupSpec(members=(0, 1, 2), senders=(0, 1),
                            msg_size=256, window=8, n_messages=4)
    small = api.GroupConfig(members=(0, 1, 2), subgroups=(spec,))
    big = api.GroupConfig(
        members=(0, 1, 2, 3),
        subgroups=(dataclasses.replace(spec, members=(0, 1, 2, 3)),))
    be = group_mod.GraphBackend()
    counts = {0: np.array([4, 4])}
    with pytest.raises(ValueError, match=r"grid point 2"):
        be.run_batch([small, small, big],
                     [counts, counts, counts])


@fast
def test_shard_count_policy():
    n_dev = len(jax.devices())
    assert placement.shard_count(0) == 1
    if n_dev == 1:
        assert placement.shard_count(8) == 1       # vmap fallback
    else:
        assert placement.shard_count(n_dev) == n_dev
        assert 8 % placement.shard_count(8) == 0
    mesh = placement.batch_mesh(1)
    assert mesh.devices.size == 1


_SHARDED_CONFORMANCE = textwrap.dedent("""
    import dataclasses
    import numpy as np
    import jax
    from repro import api
    from repro.core import placement

    assert len(jax.devices()) == 4, jax.devices()
    assert placement.shard_count(8) == 4

    def _cfg(**kw):
        base = dict(n_senders=2, msg_size=512, window=8, n_messages=8)
        base.update(kw)
        n = base.pop("n_nodes", 4)
        return api.single_group(n, **base)

    def check(make_cfg, backend, windows):
        reports = api.Group(make_cfg()).run_batch(backend=backend,
                                                  windows=windows)
        for w, rb in zip(windows, reports):
            # the grid's output lives on (was computed by) all 4 devices
            assert rb.extras["batch_devices"] == 4, (backend, w)
            base = make_cfg()
            subs = tuple(dataclasses.replace(s, window=w)
                         for s in base.subgroups)
            gi = api.Group(dataclasses.replace(base, subgroups=subs))
            ri = gi.run(backend=backend)
            assert (rb.delivered_app_msgs, rb.nulls_sent, rb.rounds) == \\
                (ri.delivered_app_msgs, ri.nulls_sent, ri.rounds), \\
                (backend, w)
            for gid, log in gi.delivery_logs.items():
                lb = rb.extras["delivery_logs"][gid]
                assert lb.delivered_seq == log.delivered_seq, (backend, w)
                assert all(np.array_equal(x, y)
                           for x, y in zip(lb.is_app, log.is_app)), \\
                    (backend, w)

    # heterogeneous 2-subgroup config: exercises the MASKED sharded path
    def _hetero():
        spec_a = api.SubgroupSpec(members=(0, 1, 2), senders=(0, 1),
                                  msg_size=512, window=8, n_messages=6)
        spec_b = api.SubgroupSpec(members=(0, 1, 2, 3), senders=(2, 3),
                                  msg_size=256, window=4, n_messages=4)
        return api.GroupConfig(members=(0, 1, 2, 3),
                               subgroups=(spec_a, spec_b))

    check(_cfg, "graph", [4, 6, 8, 12, 16, 24, 32, 48])
    check(_cfg, "pallas", [4, 6, 8, 12])
    check(_hetero, "graph", [4, 8, 16, 32])
    print("SHARDED-OK")
""")


def test_run_batch_shards_over_virtual_devices_bit_identically():
    """The multi-device path: grid points shard_mapped over 4 virtual
    CPU devices must be bit-identical to sequential single-device runs —
    on graph AND pallas (the kernel path needs check_vma off in
    shard_map), including a heterogeneous masked multi-subgroup stack.
    Runs in a subprocess because XLA_FLAGS must be set before jax
    initializes (excluded from -m fast; the full tier-1 suite covers it)."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=4").strip()
    src = str(Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", _SHARDED_CONFORMANCE],
                          capture_output=True, text=True, timeout=600,
                          env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED-OK" in proc.stdout
