"""Jit'd public wrappers around the Pallas kernels.

These adapt model-layout tensors (B, S, H, D) to the kernels' flattened
layouts, pad sequences to block multiples, and select interpret mode
automatically (interpret=True off-TPU so the kernels VALIDATE on CPU; on a
real TPU backend they compile to Mosaic).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import rmsnorm as _rn
from repro.kernels import smc_sweep as _ss
from repro.kernels import ssd_scan as _sc


def _interpret() -> bool:
    # The single compile-vs-interpret policy lives in
    # kernels.smc_sweep._auto_interpret (kernels callable without this
    # wrapper layer need it too); this is the same decision.
    return _ss._auto_interpret(None)


def flash_attention(q, k, v, *, causal: bool = True, bq: int = 128,
                    bk: int = 128):
    """q: (B, S, Hq, D); k/v: (B, S, Hkv, D) -> (B, S, Hq, D)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    group = hq // hkv
    pad = (-s) % max(bq, bk)
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s + pad, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s + pad, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s + pad, d)
    of = _fa.flash_attention_flat(qf, kf, vf, group=group, causal=causal,
                                  bq=bq, bk=bk, interpret=_interpret())
    out = of.reshape(b, hq, s + pad, d).transpose(0, 2, 1, 3)
    return out[:, :s]


def flash_decode(q, k_cache, v_cache, kv_len, *, bk: int = 512):
    """q: (B, Hq, D); caches: (B, S, Hkv, D); kv_len: scalar."""
    b, hq, d = q.shape
    s, hkv = k_cache.shape[1], k_cache.shape[2]
    group = hq // hkv
    pad = (-s) % bk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    qf = q.reshape(b * hq, 1, d)
    kf = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s + pad, d)
    vf = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, s + pad, d)
    of = _fd.flash_decode_flat(qf, kf, vf, kv_len, group=group, bk=bk,
                               interpret=_interpret())
    return of.reshape(b, hq, d)


def ssd_scan(x, dt, a_log, b, c, d_skip, dt_bias, chunk: int):
    """Mamba2 SSD scan; same signature as models.ssm.ssd_chunked."""
    return _sc.ssd_scan_pallas(x, dt, a_log, b, c, d_skip, dt_bias, chunk,
                               interpret=_interpret())


def rms_norm(x, weight, eps: float = 1e-6, *, rows: int = 256):
    """x: (..., d) -> same shape."""
    shape = x.shape
    t = 1
    for dim in shape[:-1]:
        t *= dim
    xf = x.reshape(t, shape[-1])
    pad = (-t) % rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    out = _rn.rms_norm_pallas(xf, weight, eps, rows=rows,
                              interpret=_interpret())
    return out[:t].reshape(shape)


def rms_norm_residual(x, residual, weight, eps: float = 1e-6, *,
                      rows: int = 256):
    shape = x.shape
    t = 1
    for dim in shape[:-1]:
        t *= dim
    xf = x.reshape(t, shape[-1])
    rf = residual.reshape(t, shape[-1])
    pad = (-t) % rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
        rf = jnp.pad(rf, ((0, pad), (0, 0)))
    o, r = _rn.rms_norm_residual_pallas(xf, rf, weight, eps, rows=rows,
                                        interpret=_interpret())
    return o[:t].reshape(shape), r[:t].reshape(shape)


def smc_sweep(counters, processed):
    """Batched receive-predicate sweep (see kernels.smc_sweep).  The kernel
    pads the sender axis to whole tiles internally."""
    return _ss.smc_sweep_pallas(counters, processed, interpret=_interpret())


def smc_sweep_watermark(published, processed, *, window: int, valid=None):
    """Receive sweep from published watermarks only — the counter ring is
    rebuilt inside the kernel tile, so no (S, W) array is materialized
    (see kernels.smc_sweep).  The Group ``pallas`` backend's per-round
    receive predicate.  ``valid`` masks padded (member, sender) lanes in
    stacked multi-subgroup execution."""
    return _ss.smc_sweep_watermark_pallas(published, processed,
                                          window=window, valid=valid,
                                          interpret=_interpret())
