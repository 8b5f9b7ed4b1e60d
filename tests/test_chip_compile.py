"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

Each test lowers a kernel (or the whole pallas scan program that calls
one) for a described ``v5e:2x2`` topology and compiles it with the TPU
compiler, which is installed here and needs no chip.  Nothing runs; what
Mosaic would refuse on the chip (a block not aligned to the ``(8, 128)``
tiling, an op with no TPU lowering) fails here.  Each compiled program
must hold the Mosaic kernel (``tpu_custom_call``).  The serve decode
step is compiled too, to check the rounding it asks the compiler for.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.  Off the TPU the kernel wrappers pick interpret mode,
so the tests call the kernels with ``interpret=False`` or steer the
wrappers' choice themselves.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import group as group_mod
from repro.kernels import ops
from repro.kernels import smc_sweep as ss

# qwen1.5-0.5b decode widths: 16 heads (kv 16) of 64, d_model 1024; an
# engine of 8 slots with a 1024-token cache.
QWEN_SLOTS, QWEN_LEN, QWEN_HEADS, QWEN_HEAD_DIM, QWEN_D = 8, 1024, 16, 64, \
    1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compile cache off: an
    entry compiled for a chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture
def compiled_wrappers(monkeypatch):
    """Make the ``ops`` wrappers (and so the pallas receive predicate)
    choose the Mosaic path, as they do on a TPU."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)


def _sds(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_smc_watermark_kernel_compiles_for_v5e(one_chip):
    _assert_mosaic(
        lambda p, q: ss.smc_sweep_watermark_pallas(p, q, window=100,
                                                   interpret=False),
        _sds(one_chip, (256,)), _sds(one_chip, (256,)))


def test_smc_watermark_masked_kernel_compiles_for_v5e(one_chip):
    _assert_mosaic(
        lambda p, q, v: ss.smc_sweep_watermark_pallas(
            p, q, window=100, valid=v, interpret=False),
        _sds(one_chip, (256,)), _sds(one_chip, (256,)),
        _sds(one_chip, (256,), jnp.bool_))


def test_smc_ring_kernel_compiles_for_v5e(one_chip):
    _assert_mosaic(
        lambda c, q: ss.smc_sweep_pallas(c, q, interpret=False),
        _sds(one_chip, (16, 100)), _sds(one_chip, (16,)))


def test_pallas_scan_program_compiles_at_paper_shape(one_chip,
                                                     compiled_wrappers):
    """G=1, N=S=16, W=100, 1000 messages a sender (1070 rounds): the
    whole program a pallas ``Group.run`` of the paper's testbed runs."""
    program = group_mod._scan_program.__wrapped__(
        1, 16, 16, (100,), False, True, "pallas")
    _assert_mosaic(program, _sds(one_chip, (1, 1070, 16)),
                   _sds(one_chip, (1, 6), jnp.float32))


def test_pallas_scan_program_compiles_for_masked_stack(one_chip,
                                                       compiled_wrappers):
    """A G=4 stack of unequal subgroups: padded lanes under masks, the
    kernel under vmap over G."""
    program = group_mod._scan_program.__wrapped__(
        4, 16, 16, (100, 50, 32, 16), True, True, "pallas")
    _assert_mosaic(program, _sds(one_chip, (4, 300, 16)),
                   _sds(one_chip, (4, 6), jnp.float32),
                   _sds(one_chip, (4, 16), jnp.bool_),
                   _sds(one_chip, (4, 16), jnp.bool_))


def test_flash_decode_compiles_at_qwen_widths(one_chip, compiled_wrappers):
    cache = (QWEN_SLOTS, QWEN_LEN, QWEN_HEADS, QWEN_HEAD_DIM)
    _assert_mosaic(
        ops.flash_decode,
        _sds(one_chip, (QWEN_SLOTS, QWEN_HEADS, QWEN_HEAD_DIM),
             jnp.bfloat16),
        _sds(one_chip, cache, jnp.bfloat16),
        _sds(one_chip, cache, jnp.bfloat16),
        _sds(one_chip, ()))


def test_rms_norm_compiles_at_qwen_widths(one_chip, compiled_wrappers):
    _assert_mosaic(
        ops.rms_norm,
        _sds(one_chip, (QWEN_SLOTS, 1, QWEN_D), jnp.bfloat16),
        _sds(one_chip, (QWEN_D,), jnp.bfloat16))


def test_serve_decode_rounds_every_op_on_v5e(one_chip):
    """The serve decode step compiles for the chip with its bf16 rounding
    kept op by op (``reduce-precision``), which the TPU compiler drops
    inside fusions by default: the per-round loop and the fused serve
    program then agree bit for bit whatever each fuses."""
    from repro.models import layers, registry
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg = registry.get("qwen1.5-0.5b").cfg.reduced()

    def abstract(specs):
        return jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                            specs,
                            is_leaf=lambda x: isinstance(x, layers.ParamSpec))

    params = abstract(registry.param_specs(cfg))
    eng = ServeEngine("qwen1.5-0.5b", params, cfg,
                      EngineConfig(max_batch=4, max_len=64))
    args = (params, abstract(eng.cache_specs), _sds(one_chip, (4, 1)),
            _sds(one_chip, (4,)), _sds(one_chip, (4,), jnp.bool_))
    as_written = eng.decode.lower(*args).compile().as_text()
    default = jax.jit(eng._decode_body).lower(*args).compile().as_text()
    assert "reduce-precision" in as_written
    assert "reduce-precision" not in default
