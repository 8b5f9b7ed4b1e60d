"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode.

Every kernel is validated against ref.py across a grid of shapes and both
bf16/f32; the SSD chunked algorithm is additionally validated against the
definitional step-by-step recurrence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

pytestmark = pytest.mark.fast

jax.config.update("jax_platform_name", "cpu")


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def _rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 128, 4, 4, 64),
    (2, 256, 4, 2, 64),
    (1, 384, 8, 1, 128),   # MQA + non-pow2 seq blocks
    (2, 128, 6, 2, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_matches_ref(b, s, hq, hkv, d, dtype, causal):
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    q = _rand(k1, (b, s, hq, d), dtype)
    k = _rand(k2, (b, s, hkv, d), dtype)
    v = _rand(k3, (b, s, hkv, d), dtype)
    got = ops.flash_attention(q, k, v, causal=causal)
    group = hq // hkv
    qf = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    want = ref.flash_attention_ref(qf, kf, vf, group=group, causal=causal)
    want = want.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


def test_flash_attention_unpadded_seq():
    # seq not a multiple of the block: wrapper pads, result must match
    b, s, h, d = 1, 200, 2, 64
    keys = jax.random.split(jax.random.key(1), 3)
    q, k, v = (_rand(kk, (b, s, h, d), jnp.float32) for kk in keys)
    got = ops.flash_attention(q, k, v, causal=True)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    want = ref.flash_attention_ref(qf, kf, vf, group=1, causal=True)
    want = want.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,smax,hq,hkv,d,kvlen", [
    (2, 512, 4, 4, 64, 512),
    (2, 512, 4, 2, 64, 300),    # partially-filled cache
    (1, 1024, 8, 1, 128, 7),    # nearly-empty cache
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_ref(b, smax, hq, hkv, d, kvlen, dtype):
    k1, k2, k3 = jax.random.split(jax.random.key(2), 3)
    q = _rand(k1, (b, hq, d), dtype)
    k = _rand(k2, (b, smax, hkv, d), dtype)
    v = _rand(k3, (b, smax, hkv, d), dtype)
    got = ops.flash_decode(q, k, v, jnp.int32(kvlen))
    group = hq // hkv
    qf = q.reshape(b * hq, 1, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hkv, smax, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hkv, smax, d)
    want = ref.flash_decode_ref(qf, kf, vf, kvlen, group=group)
    want = want.reshape(b, hq, d)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tol(dtype))


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,n,g,chunk", [
    (1, 64, 2, 16, 16, 1, 16),
    (2, 128, 4, 32, 64, 2, 32),
    (1, 96, 2, 64, 128, 1, 32),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_matches_chunked_ref(b, s, h, p, n, g, chunk, dtype):
    keys = jax.random.split(jax.random.key(3), 7)
    x = _rand(keys[0], (b, s, h, p), dtype)
    dt = _rand(keys[1], (b, s, h), jnp.float32) * 0.5
    a_log = jax.random.uniform(keys[2], (h,), minval=-1.0, maxval=0.5)
    bb = _rand(keys[3], (b, s, g, n), dtype) * 0.3
    cc = _rand(keys[4], (b, s, g, n), dtype) * 0.3
    d_skip = jax.random.uniform(keys[5], (h,))
    dt_bias = jax.random.uniform(keys[6], (h,), minval=-0.5, maxval=0.5)
    y_got, st_got = ops.ssd_scan(x, dt, a_log, bb, cc, d_skip, dt_bias,
                                 chunk)
    y_ref, st_ref = ref.ssd_scan_ref(x, dt, a_log, bb, cc, d_skip, dt_bias,
                                     chunk)
    tol = dict(rtol=5e-2, atol=5e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(y_got, np.float32),
                               np.asarray(y_ref, np.float32), **tol)
    np.testing.assert_allclose(np.asarray(st_got), np.asarray(st_ref),
                               rtol=1e-3, atol=1e-3)


def test_ssd_chunked_matches_sequential():
    """The chunked algorithm == the definitional per-step recurrence."""
    b, s, h, p, n, g = 1, 32, 2, 8, 16, 1
    keys = jax.random.split(jax.random.key(4), 7)
    x = _rand(keys[0], (b, s, h, p), jnp.float32)
    dt = _rand(keys[1], (b, s, h), jnp.float32) * 0.5
    a_log = jax.random.uniform(keys[2], (h,), minval=-1.0, maxval=0.5)
    bb = _rand(keys[3], (b, s, g, n), jnp.float32) * 0.3
    cc = _rand(keys[4], (b, s, g, n), jnp.float32) * 0.3
    d_skip = jax.random.uniform(keys[5], (h,))
    dt_bias = jnp.zeros((h,))
    y_c, st_c = ref.ssd_scan_ref(x, dt, a_log, bb, cc, d_skip, dt_bias, 8)
    y_s, st_s = ref.ssd_sequential_ref(x, dt, a_log, bb, cc, d_skip,
                                       dt_bias)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st_c), np.asarray(st_s),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t,d", [(256, 128), (300, 512), (1024, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_norm_matches_ref(t, d, dtype):
    k1, k2 = jax.random.split(jax.random.key(5))
    x = _rand(k1, (t, d), dtype)
    w = jax.random.uniform(k2, (d,), minval=0.5, maxval=1.5).astype(dtype)
    got = ops.rms_norm(x, w)
    want = ref.rms_norm_ref(x, w)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_norm_residual_matches_ref(dtype):
    k1, k2, k3 = jax.random.split(jax.random.key(6), 3)
    x = _rand(k1, (512, 256), dtype)
    r = _rand(k2, (512, 256), dtype)
    w = jax.random.uniform(k3, (256,), minval=0.5, maxval=1.5).astype(dtype)
    got_o, got_r = ops.rms_norm_residual(x, r, w)
    want_o, want_r = ref.rms_norm_residual_ref(x, r, w)
    np.testing.assert_allclose(np.asarray(got_o, np.float32),
                               np.asarray(want_o, np.float32),
                               **_tol(dtype))
    np.testing.assert_allclose(np.asarray(got_r, np.float32),
                               np.asarray(want_r, np.float32),
                               **_tol(dtype))


# ---------------------------------------------------------------------------
# smc sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,w", [(8, 16), (16, 100), (5, 64)])
def test_smc_sweep_matches_ref(s, w):
    rng = np.random.default_rng(7)
    processed = rng.integers(0, 50, size=s)
    published = processed + rng.integers(0, w + 1, size=s)
    counters = np.full((s, w), -1, dtype=np.int64)
    for i in range(s):
        for k in range(published[i]):
            counters[i, k % w] = k // w
    got = ops.smc_sweep(jnp.asarray(counters), jnp.asarray(processed))
    want = ref.smc_sweep_ref(jnp.asarray(counters), jnp.asarray(processed))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got), published)


@pytest.mark.parametrize("s", [3, 5, 7, 9])
def test_smc_sweep_pallas_pads_nondivisible_senders(s):
    """The kernel itself (not just the ops wrapper) pads the sender axis
    to whole (8, 128) tiles: 3- or 5-sender subgroups run."""
    from repro.kernels import smc_sweep as ss
    rng = np.random.default_rng(11)
    w = 16
    processed = rng.integers(0, 20, size=s)
    published = processed + rng.integers(0, w + 1, size=s)
    counters = np.asarray(ss.counters_from_counts(published, w))
    got = ss.smc_sweep_pallas(jnp.asarray(counters), jnp.asarray(processed),
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(got), published)


@pytest.mark.parametrize("s,w", [(8, 16), (5, 32), (16, 100)])
def test_smc_watermark_kernel_matches_materialized_ring(s, w):
    """The in-kernel ring reconstruction is the same fixed point as
    sweeping an explicitly materialized counters_from_counts ring."""
    from repro.kernels import smc_sweep as ss
    rng = np.random.default_rng(13)
    processed = rng.integers(0, 50, size=s)
    published = processed + rng.integers(0, w + 1, size=s)
    via_ring = ops.smc_sweep(
        ss.counters_from_counts(jnp.asarray(published), w),
        jnp.asarray(processed))
    via_watermark = ops.smc_sweep_watermark(
        jnp.asarray(published), jnp.asarray(processed), window=w)
    np.testing.assert_array_equal(np.asarray(via_ring),
                                  np.asarray(via_watermark))
    np.testing.assert_array_equal(np.asarray(via_watermark), published)


@pytest.mark.parametrize("s,w", [(8, 16), (5, 32), (13, 8)])
def test_smc_watermark_kernel_validity_mask(s, w):
    """Member/sender-axis padding in the stacked path arrives at the
    kernel as a flattened lane mask: invalid lanes return ``processed``
    unchanged — whatever garbage their published watermark holds — while
    valid lanes are bit-identical to the unmasked kernel."""
    from repro.kernels import smc_sweep as ss
    rng = np.random.default_rng(17)
    processed = rng.integers(0, 50, size=s)
    published = processed + rng.integers(0, w + 1, size=s)
    valid = rng.integers(0, 2, size=s).astype(bool)
    # poison invalid lanes: advancement there would corrupt padded slots
    published = np.where(valid, published, processed + w)
    got = ss.smc_sweep_watermark_pallas(
        jnp.asarray(published), jnp.asarray(processed), window=w,
        valid=jnp.asarray(valid), interpret=True)
    want = np.where(valid, published, processed)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_smc_watermark_kernel_full_mask_matches_unmasked():
    from repro.kernels import smc_sweep as ss
    rng = np.random.default_rng(19)
    s, w = 7, 16
    processed = rng.integers(0, 20, size=s)
    published = processed + rng.integers(0, w + 1, size=s)
    masked = ss.smc_sweep_watermark_pallas(
        jnp.asarray(published), jnp.asarray(processed), window=w,
        valid=jnp.ones(s, bool), interpret=True)
    plain = ss.smc_sweep_watermark_pallas(
        jnp.asarray(published), jnp.asarray(processed), window=w,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(masked), np.asarray(plain))


# ---------------------------------------------------------------------------
# model integration: pallas impl == xla impl end to end
# ---------------------------------------------------------------------------

def test_attention_impl_parity():
    from repro.models import attention, registry
    import dataclasses as dc
    from repro.models import layers as L
    from repro.models.runtime import Runtime
    cfg = registry.get("qwen3-1.7b").cfg.reduced()
    cfg = dc.replace(cfg, head_dim=64)
    p = L.init_tree(attention.attn_specs(cfg), jax.random.key(8))
    x = _rand(jax.random.key(9), (2, 128, cfg.d_model), jnp.float32)
    out_x = attention.full_attention(p, cfg, x, impl="xla")
    out_p = attention.full_attention(p, cfg, x, impl="pallas")
    np.testing.assert_allclose(np.asarray(out_x, np.float32),
                               np.asarray(out_p, np.float32),
                               rtol=2e-2, atol=2e-2)
