"""SMC receive-sweep as a Pallas kernel — the opportunistic-batching inner
loop (paper Sec. 3.2) expressed as a TPU data-movement kernel.

Given every sender's slot-counter ring (S, W) and the per-sender processed
counts, compute in ONE pass (a) the new visible count per sender (the
contiguous-slot scan of the receive predicate) and (b) the round-robin
received_num prefix — i.e. a whole receive-predicate iteration for all
senders, fused.  The polling area streams HBM->VMEM in (window x lanes)
tiles; this is the structural analogue of keeping the SMC polling area
cache-resident (Fig. 6's w=100 sweet spot).

Two entry points:

* :func:`smc_sweep_pallas` — sweeps an explicit (S, W) counter ring (the
  real SMC data structure, e.g. one built by :func:`repro.core.smc.publish`).
* :func:`smc_sweep_watermark_pallas` — sweeps from per-sender published
  watermarks only: the counter tile the ring would hold is reconstructed
  *inside* the kernel (registers/VMEM), so nothing (S, W)-shaped is ever
  materialized in HBM.  This is the Group hot path: per protocol round it
  moves O(S) instead of O(S*W) bytes.

Layout: the sender (lane) axis is padded to whole int32 ``(8, 128)`` vreg
tiles and viewed as ``(rows, 128)``; each grid step sweeps one tile (the
explicit ring as a ``(W, 8, 128)`` block, slot-major).  Any sender count
runs and results are sliced back.  The kernels compile to Mosaic on TPU
and fall back to interpret mode elsewhere (``interpret=None`` = auto).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_ROWS, _LANES = 8, 128            # one int32 vreg tile
_TILE = _ROWS * _LANES


def _auto_interpret(interpret) -> bool:
    """Compiled (Mosaic) path on TPU, interpret fallback elsewhere."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _contiguous_run(counter_at, processed, window: int):
    """Shared predicate core: length of the contiguous visible run starting
    at ``processed``, i.e. the first candidate ``j`` in ``[0, W)`` whose
    message ``k = processed + j`` is missing from slot ``k % W`` (``W`` if
    none is).  ``counter_at(slot)`` returns that slot's counter for every
    lane of the tile.  The loop walks the ring slot by slot, and each slot
    holds exactly one candidate, so no lane ever gathers."""

    def body(slot, run):
        j = (slot - processed) % window           # the candidate in `slot`
        have = counter_at(slot) >= (processed + j) // window
        return jnp.minimum(run, jnp.where(have, window, j))

    return jax.lax.fori_loop(0, window, body,
                             jnp.full(processed.shape, window, jnp.int32))


def _sweep_kernel(counters_ref, processed_ref, visible_ref, *, window: int):
    processed = processed_ref[...]                # (8, 128) int32
    visible_ref[...] = processed + _contiguous_run(
        lambda slot: counters_ref[slot], processed, window)


def _watermark_run(published, processed, window: int):
    """Shared tile core of the watermark kernels: rebuild each counter slot
    in-registers from the published watermark (see
    :func:`counters_from_counts` for the ring state being reproduced — no
    (S, W) array crosses HBM) and return the contiguous visible run."""
    return _contiguous_run(
        lambda slot: jnp.where(published > slot,
                               (published - 1 - slot) // window, -1),
        processed, window)


def _watermark_kernel(published_ref, processed_ref, visible_ref, *,
                      window: int):
    """Receive sweep from published watermarks, ring rebuilt in-kernel."""
    published = published_ref[...]                # (8, 128) int32
    processed = processed_ref[...]                # (8, 128) int32
    visible_ref[...] = processed + _watermark_run(published, processed,
                                                  window)


def _watermark_masked_kernel(published_ref, processed_ref, valid_ref,
                             visible_ref, *, window: int):
    """:func:`_watermark_kernel` with an explicit per-lane validity mask —
    the stacked multi-subgroup path flattens a padded (member, sender)
    plane into the lane axis, so padded member rows AND padded sender
    ranks arrive here as lanes whose ring must stay untouched.  An invalid
    lane returns ``processed`` unchanged (no advancement), whatever its
    published watermark holds."""
    published = published_ref[...]                # (8, 128) int32
    processed = processed_ref[...]                # (8, 128) int32
    valid = valid_ref[...]                        # (8, 128) int32 (0/1)
    run = _watermark_run(published, processed, window)
    visible_ref[...] = processed + jnp.where(valid > 0, run, 0)


def counters_from_counts(published, window: int):
    """Materialize the SMC slot-counter ring a receiver would observe after
    ``published`` messages from each sender.

    published: (S,) int32 counts -> (S, W) int32 counters.  Slot ``j``
    holds the counter of the latest message index ``k < published`` with
    ``k % W == j`` (``-1`` if the slot was never written) — exactly the
    state :func:`repro.core.smc.publish` builds incrementally.  Prefer
    :func:`smc_sweep_watermark_pallas` on the hot path, which computes the
    same tile inside the kernel instead of materializing it here.
    """
    published = jnp.asarray(published, jnp.int32)
    slots = jnp.arange(window, dtype=jnp.int32)[None, :]
    pub = published[:, None]
    return jnp.where(pub > slots, (pub - 1 - slots) // window, -1)


def _to_tiles(x, fill: int):
    """(..., S) -> (..., rows, 128) int32: the lane axis padded with
    ``fill`` to whole (8, 128) tiles (padding lanes sweep an empty ring)."""
    x = jnp.asarray(x, jnp.int32)
    pad = (-x.shape[-1]) % _TILE
    x = jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),),
                constant_values=fill)
    return x.reshape(x.shape[:-1] + (-1, _LANES))


_LANE_SPEC = pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0))


def smc_sweep_pallas(counters, processed, *, interpret=None):
    """counters: (S, W) int32 slot counters; processed: (S,) int32.
    Returns visible counts (S,) — the batched receive for every sender.

    Any S runs: the sender axis is padded to whole (8, 128) tiles (padding
    lanes sweep an empty ring) and the result sliced back.
    """
    s, w = counters.shape
    ring = _to_tiles(jnp.asarray(counters).T, fill=-1)     # (W, rows, 128)
    out = pl.pallas_call(
        functools.partial(_sweep_kernel, window=w),
        grid=(ring.shape[1] // _ROWS,),
        in_specs=[pl.BlockSpec((w, _ROWS, _LANES), lambda i: (0, i, 0)),
                  _LANE_SPEC],
        out_specs=_LANE_SPEC,
        out_shape=jax.ShapeDtypeStruct(ring.shape[1:], jnp.int32),
        interpret=_auto_interpret(interpret),
    )(ring, _to_tiles(processed, fill=0))
    return out.reshape(-1)[:s]


def smc_sweep_watermark_pallas(published, processed, *, window: int,
                               valid=None, interpret=None):
    """published/processed: (S,) int32 -> visible counts (S,).

    Same fixed point as :func:`smc_sweep_pallas` over
    :func:`counters_from_counts`, but the ring tile lives only inside the
    kernel: HBM traffic per call is O(S), not O(S*W).  This is what the
    ``pallas`` Group backend scans every protocol round.

    ``valid`` (optional, (S,) bool/int): per-lane validity for stacked
    padded execution.  The lane axis here is really a flattened
    (member, sender) plane when driven by the Group backends, so the mask
    covers member-axis padding as well as sender-axis padding: an invalid
    lane's result is its ``processed`` count unchanged.  (The tile padding
    below is the third, kernel-private padding level.)
    """
    s = published.shape[0]
    operands = [published, processed]
    kernel = _watermark_kernel
    if valid is not None:
        operands.append(valid)
        kernel = _watermark_masked_kernel
    operands = [_to_tiles(x, fill=0) for x in operands]
    rows = operands[0].shape[0]
    out = pl.pallas_call(
        functools.partial(kernel, window=window),
        grid=(rows // _ROWS,),
        in_specs=[_LANE_SPEC] * len(operands),
        out_specs=_LANE_SPEC,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
        interpret=_auto_interpret(interpret),
    )(*operands)
    return out.reshape(-1)[:s]
