"""Hot-path coverage for the compiled graph/pallas substrate:

* jit-cache behaviour — a second ``Group.run`` with the same static key
  ``(n_members, n_senders, window, null_send, backend)`` must NOT re-trace
  the scan program (asserted through the trace-counter side effect in
  ``group.TRACE_EVENTS``);
* vectorized delivery-log reconstruction — property-tested against the
  old per-message reference loop on random traces;
* batched multi-scenario execution — ``Group.run_batch`` must reproduce
  looped ``Group.run`` exactly (identical RunReport counts and
  byte-identical delivery logs) on every backend, including the
  sequential-fallback ``des`` path.
"""

import dataclasses

import numpy as np
import pytest

from repro import api
from repro.core import group as group_mod
from repro.core import sst

pytestmark = pytest.mark.fast


def _cfg(**kw):
    base = dict(n_senders=3, msg_size=1024, window=16, n_messages=15)
    base.update(kw)
    n = base.pop("n_nodes", 4)
    return api.single_group(n, **base)


def _logs_equal(a, b):
    return (a.n_senders == b.n_senders
            and a.delivered_seq == b.delivered_seq
            and len(a.is_app) == len(b.is_app)
            and all(np.array_equal(x, y)
                    for x, y in zip(a.is_app, b.is_app)))


# ---------------------------------------------------------------------------
# jit cache: compile once per static key
# ---------------------------------------------------------------------------

def test_second_run_with_same_static_key_does_not_retrace():
    cfg = _cfg(window=13)                # a window no other test uses
    api.Group(cfg).run(backend="graph")  # may or may not trace (cold cache)
    before = len(group_mod.TRACE_EVENTS)
    r = api.Group(cfg).run(backend="graph")
    assert len(group_mod.TRACE_EVENTS) == before, \
        "same static key re-traced the scan program"
    assert r.delivered_app_msgs == 4 * 3 * 15


def test_changed_static_key_traces_again():
    cfg = _cfg(window=13)
    api.Group(cfg).run(backend="graph")
    before = len(group_mod.TRACE_EVENTS)
    sub = dataclasses.replace(cfg.subgroups[0], window=11)
    api.Group(cfg).run(backend="graph", subgroups=(sub,))
    assert len(group_mod.TRACE_EVENTS) == before + 1


def test_backends_do_not_share_scan_programs():
    cfg = _cfg(window=13)
    api.Group(cfg).run(backend="graph")
    api.Group(cfg).run(backend="pallas")
    before = len(group_mod.TRACE_EVENTS)
    api.Group(cfg).run(backend="pallas")   # warm for pallas too
    assert len(group_mod.TRACE_EVENTS) == before


# ---------------------------------------------------------------------------
# vectorized _reconstruct == the old per-message loop (property test)
# ---------------------------------------------------------------------------

def _reconstruct_reference(spec, batches, app_pub, nulls):
    """The pre-vectorization implementation, kept verbatim as the oracle."""
    n_s = len(spec.senders)
    rounds = batches.shape[0]
    is_app = [[] for _ in range(n_s)]
    pub_round = [[] for _ in range(n_s)]
    for r in range(rounds):
        for s in range(n_s):
            for _ in range(int(app_pub[r, s])):
                is_app[s].append(True)
                pub_round[s].append(r)
            for _ in range(int(nulls[r, s])):
                is_app[s].append(False)
                pub_round[s].append(r)
    delivered_num = np.cumsum(batches, axis=0) - 1
    final = delivered_num[-1] if rounds else np.full(len(spec.members), -1)
    delivered = {node: int(final[pos])
                 for pos, node in enumerate(spec.members)}
    lat = []
    if rounds:
        col = delivered_num[:, 0]
        for seq in range(int(final[0]) + 1):
            rank, idx = seq % n_s, seq // n_s
            if not is_app[rank][idx]:
                continue
            lat.append((pub_round[rank][idx], int(np.searchsorted(col, seq))))
    log = group_mod.DeliveryLog(
        n_senders=n_s,
        is_app=[np.array(a, dtype=bool) for a in is_app],
        delivered_seq=delivered)
    return log, lat


def _random_trace(rng, n_m, n_s, rounds):
    """A random (batches, app_pub, nulls) trace whose delivered prefixes
    stay inside the published round-robin order (the protocol invariant
    _reconstruct may assume)."""
    app_pub = rng.integers(0, 3, size=(rounds, n_s))
    nulls = rng.integers(0, 2, size=(rounds, n_s))
    totals = app_pub.sum(axis=0) + nulls.sum(axis=0)
    max_count = int(sst.rr_prefix(totals))       # valid seqs: 0..max_count-1
    batches = np.zeros((rounds, n_m), dtype=np.int64)
    for pos in range(n_m):
        fin = int(rng.integers(-1, max_count))
        col = np.sort(rng.integers(-1, fin + 1, size=rounds))
        col[-1] = fin
        batches[:, pos] = np.diff(np.concatenate([[-1], col]))
    return batches, app_pub, nulls


def test_vectorized_reconstruct_matches_reference_loop_on_random_traces():
    rng = np.random.default_rng(20260730)
    for case in range(50):
        n_m = int(rng.integers(1, 6))
        n_s = int(rng.integers(1, n_m + 1))
        rounds = int(rng.integers(1, 14))
        spec = api.SubgroupSpec(members=tuple(range(n_m)),
                                senders=tuple(range(n_s)),
                                msg_size=64, window=8, n_messages=0)
        batches, app_pub, nulls = _random_trace(rng, n_m, n_s, rounds)
        log_v, lat_v = group_mod.GraphBackend._reconstruct(
            spec, batches, app_pub, nulls)
        log_r, lat_r = _reconstruct_reference(spec, batches, app_pub, nulls)
        assert _logs_equal(log_v, log_r), f"case {case}: logs diverge"
        assert [tuple(p) for p in lat_v] == lat_r, \
            f"case {case}: latency round-pairs diverge"


def test_reconstruct_empty_trace():
    spec = api.SubgroupSpec(members=(0, 1), senders=(0,), msg_size=64,
                            window=4, n_messages=0)
    z = np.zeros((0, 1), np.int64)
    log, lat = group_mod.GraphBackend._reconstruct(
        spec, np.zeros((0, 2), np.int64), z, z)
    assert log.delivered_seq == {0: -1, 1: -1}
    assert len(lat) == 0


# ---------------------------------------------------------------------------
# run_batch == looped run (cross-backend conformance)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["graph", "pallas", "des"])
def test_run_batch_matches_looped_run_on_window_grid(backend):
    windows = [4, 8, 16]
    g = api.Group(_cfg())
    reports = g.run_batch(backend=backend, windows=windows)
    assert len(reports) == len(windows)
    for w, rb in zip(windows, reports):
        gi = api.Group(_cfg(window=w))
        ri = gi.run(backend=backend)
        assert (rb.delivered_app_msgs, rb.delivered_null_msgs,
                rb.nulls_sent, rb.rdma_writes, rb.rounds) == \
            (ri.delivered_app_msgs, ri.delivered_null_msgs,
             ri.nulls_sent, ri.rdma_writes, ri.rounds), (backend, w)
        assert rb.duration_us == pytest.approx(ri.duration_us, rel=1e-6)
        for gid, log in gi.delivery_logs.items():
            assert _logs_equal(rb.extras["delivery_logs"][gid], log), \
                (backend, w, gid)


def test_run_batch_null_send_grid_matches_single_runs():
    pats = (((0, 1), api.SenderPattern(active=False)),)
    g = api.Group(_cfg(patterns=pats, n_messages=10))
    reports = g.run_batch(backend="graph", null_send=[True, False])
    for flag, rb in zip([True, False], reports):
        cfg_i = dataclasses.replace(
            g.cfg, flags=dataclasses.replace(g.cfg.flags, null_send=flag))
        gi = api.Group(cfg_i)
        ri = gi.run(backend="graph")
        assert rb.nulls_sent == ri.nulls_sent
        assert rb.delivered_app_msgs == ri.delivered_app_msgs
        for gid, log in gi.delivery_logs.items():
            assert _logs_equal(rb.extras["delivery_logs"][gid], log)
    # the grid actually exercised both flag values
    assert reports[0].nulls_sent > 0
    assert reports[1].nulls_sent == 0


def test_run_batch_n_messages_grid():
    msgs = [5, 10, 20]
    reports = api.Group(_cfg()).run_batch(backend="graph", n_messages=msgs)
    for m, rb in zip(msgs, reports):
        assert rb.delivered_app_msgs == 4 * 3 * m
        assert not rb.stalled


def test_run_batch_multi_subgroup_conforms():
    spec_a = api.SubgroupSpec(members=(0, 1, 2), senders=(0, 1),
                              msg_size=512, window=8, n_messages=6)
    spec_b = api.SubgroupSpec(members=(0, 1, 2, 3), senders=(2, 3),
                              msg_size=256, window=4, n_messages=4)
    cfg = api.GroupConfig(members=(0, 1, 2, 3), subgroups=(spec_a, spec_b))
    reports = api.Group(cfg).run_batch(backend="graph", windows=[4, 8])
    for w, rb in zip([4, 8], reports):
        subs = tuple(dataclasses.replace(s, window=w)
                     for s in cfg.subgroups)
        gi = api.Group(dataclasses.replace(cfg, subgroups=subs))
        ri = gi.run(backend="graph")
        assert rb.delivered_app_msgs == ri.delivered_app_msgs
        for gid, log in gi.delivery_logs.items():
            assert _logs_equal(rb.extras["delivery_logs"][gid], log)


def test_run_batch_requires_a_grid():
    with pytest.raises(ValueError):
        api.Group(_cfg()).run_batch(backend="graph")


def test_run_batch_rejects_mismatched_grid_lengths():
    with pytest.raises(ValueError):
        api.Group(_cfg()).run_batch(backend="graph", windows=[4, 8],
                                    null_send=[True])


# ---------------------------------------------------------------------------
# persistent compilation cache: one rule (repro.use_compilation_cache)
# ---------------------------------------------------------------------------

def _run_py(code, env_extra):
    """Run ``code`` in a fresh interpreter: the rule configures jax
    globally, which must not leak into this test process."""
    import os
    import pathlib
    import subprocess
    import sys

    import repro

    src = str(pathlib.Path(repro.__file__).resolve().parents[1])
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = src
    env.update(env_extra)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    return out


def test_compilation_cache_honours_jax_env(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, that directory is the cache,
    entries land there, and nothing in the checkout is configured."""
    cache = str(tmp_path / "cc" / "nested")
    _run_py(
        "import os, jax, jax.numpy as jnp, repro\n"
        "assert jax.config.jax_compilation_cache_dir is not None\n"
        f"assert repro.use_compilation_cache() == {cache!r}\n"
        f"assert jax.config.jax_compilation_cache_dir == {cache!r}\n"
        "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
        " 0.0)\n"
        "jax.jit(lambda x: x * 3 + 1)(jnp.ones(4)).block_until_ready()\n"
        f"assert os.listdir({cache!r}), 'nothing cached'\n",
        {"JAX_COMPILATION_CACHE_DIR": cache})


def test_compilation_cache_defaults_to_fixed_checkout_path():
    """Without the env var the cache is ``<checkout>/.jax_cache``: the
    same path in every process, ignored by git, and not switched on by
    merely importing the package."""
    import pathlib

    import repro

    root = pathlib.Path(repro.__file__).resolve().parents[2]
    want = str(root / ".jax_cache")
    assert repro.CHECKOUT_COMPILATION_CACHE == want
    probe = (
        "import jax, repro\n"
        "assert jax.config.jax_compilation_cache_dir is None\n"
        "print(repro.use_compilation_cache())\n"
        f"assert jax.config.jax_compilation_cache_dir == {want!r}\n")
    runs = {_run_py(probe, {}).stdout.strip() for _ in range(2)}
    assert runs == {want}
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
