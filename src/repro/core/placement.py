"""Device-placement policy for the stacked batch programs.

The batched stacked sweep (:func:`repro.core.sweep.run_stacked_batch`)
is embarrassingly data-parallel over its leading scenario-grid axis, so a
grid of B points can run one shard per device instead of one vmapped
program on a single device — the ROADMAP's sharding/multi-device step,
wired through the same `shard_map` machinery the training plane already
uses (:mod:`repro.core.gradsync`, :mod:`repro.launch.mesh`).

Policy (see :func:`shard_count`, DESIGN.md Sec. 3): shard over the
LARGEST device count that evenly divides the batch — deterministic per
process, so it is safe inside compile-cache keys; when that is 1 (single
device, or an indivisible batch) callers fall back to plain vmap —
graceful degradation on a CPU-only host.  The mesh reuses
:func:`repro.launch.mesh.make_smoke_mesh`'s "whatever devices exist"
construction (and its ``data`` axis name) when every device participates,
trimming to a prefix of ``jax.devices()`` otherwise.

The shard_map wrapper passes ``check_vma=False``: shard_map's
varying-manual-axes analysis has no rule for ``pallas_call`` and rejects
the scan carry of the pallas backend's sharded grid — and nothing here
relies on it (every output is sharded exactly like the inputs; there is
no cross-shard communication to analyze).
"""

from __future__ import annotations

from typing import Callable

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch import mesh as mesh_mod

# The batch axis rides the launch-plane's data-parallel axis name so the
# same mesh conventions serve both planes.
BATCH_AXIS = "data"


def shard_count(batch: int) -> int:
    """How many devices a B-point grid will shard over: the largest device
    count that evenly divides ``batch`` (1 = vmap fallback).  Deterministic
    per process — safe to use in compile-cache keys."""
    n_dev = len(jax.devices())
    if batch <= 0 or n_dev <= 1:
        return 1
    for d in range(min(n_dev, batch), 0, -1):
        if batch % d == 0:
            return d
    return 1


def batch_mesh(n_shards: int) -> jax.sharding.Mesh:
    """A 1-D mesh over the first ``n_shards`` devices, axis ``data``."""
    devices = jax.devices()
    if n_shards == len(devices):
        return mesh_mod.make_smoke_mesh()
    return jax.sharding.Mesh(np.asarray(devices[:n_shards]), (BATCH_AXIS,))


def shard_over_batch(fn: Callable, n_shards: int,
                     n_batched_args: int) -> Callable:
    """shard_map ``fn`` over the leading batch axis of its first
    ``n_batched_args`` positional arguments (every output is batched too).

    Each shard sees its ``B/n_shards`` slice of the grid; since grid
    points are independent there is no cross-shard communication — the
    sharded program is the vmapped program, n_shards times narrower.
    """
    mesh = batch_mesh(n_shards)
    spec = P(mesh.axis_names[0])
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=(spec,) * n_batched_args, out_specs=spec,
                         check_vma=False)
