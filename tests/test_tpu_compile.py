"""The main path's kernels and the gateway decode step, compiled for a
described TPU v5e chip at qwen3-4b's published widths.

Nothing here runs on a chip: the TPU compiler that ships with jax
compiles for a topology that is described, not attached, and refuses
what the chip would refuse (unaligned blocks, VMEM and SMEM overflow).
Interpret-mode tests cannot see those faults.

The topology is described only inside the module fixture below, never
at import: one process at a time may load the TPU library, and it
keeps it until it exits.  Every shape states its dtype, because the
suite runs with x64 on; the compiles themselves run with it off, as the
program does on the chip (under x64 a literal block index in an
index map becomes an i64, which Mosaic refuses).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import paged_kv, prefill_attn
from repro.kernels.ptc_block_matmul import (accepts, ptc_block_matmul,
                                           row_tile)
from repro.models.lm import build_gateway_step, init_model, period_plan

QWEN = get_config("qwen3-4b")
SLOTS, PAGE, PAGES_PER_SLOT = 8, 16, 64
HKV, DH = QWEN.n_kv_heads, QWEN.hd


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # not read back without a chip: keep it out of the cache
    was = (jax.config.jax_enable_compilation_cache,
           jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.config.update("jax_enable_x64", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_enable_x64", was[1])


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool(sharding, n_periods):
    stripe = SLOTS * PAGES_PER_SLOT + 1          # + the scratch page
    return _spec(sharding, (n_periods * stripe, PAGE, HKV, DH),
                 jnp.bfloat16)


def test_paged_gather_compiles(one_chip):
    n_periods = 8
    table = _spec(one_chip, (n_periods * SLOTS, PAGES_PER_SLOT), jnp.int32)
    jax.jit(lambda t, p: paged_kv.paged_gather(t, p)).lower(
        table, _pool(one_chip, n_periods)).compile()


@pytest.mark.parametrize("chunk", [1, 32])
def test_paged_scatter_rows_compiles(one_chip, chunk):
    n_periods = 8
    rows = n_periods * SLOTS * chunk
    jax.jit(lambda i, r, p: paged_kv.paged_scatter_rows(i, r, p)).lower(
        _spec(one_chip, (rows, 2), jnp.int32),
        _spec(one_chip, (rows, HKV, DH), jnp.bfloat16),
        _pool(one_chip, n_periods)).compile()


def test_prefill_attention_compiles_with_derived_block(one_chip):
    b, c, h, s = SLOTS, 32, QWEN.n_heads, PAGES_PER_SLOT * PAGE
    assert prefill_attn.kv_block(s, c, h, HKV, DH, 2) == 128
    kv = _spec(one_chip, (b, s, HKV, DH), jnp.bfloat16)
    jax.jit(lambda ln, q, k, v: prefill_attn.prefill_attention(
        ln, q, k, v)).lower(
        _spec(one_chip, (b,), jnp.int32),
        _spec(one_chip, (b, c, h, DH), jnp.bfloat16), kv, kv).compile()


def test_ptc_block_matmul_compiles_at_q_projection_grid(one_chip):
    """qwen3-4b's q projection as a 32×20 grid of k=128 blocks, over a
    token count that is not a multiple of 8 (the twin's layer path)."""
    p, q, k, t = 32, 20, 128, 37
    assert accepts(k) and row_tile(t) == t
    f32 = jnp.float32
    jax.jit(lambda x, u, s, v: ptc_block_matmul(x, u, s, v)).lower(
        _spec(one_chip, (t, q * k), f32),
        _spec(one_chip, (p, q, k, k), f32),
        _spec(one_chip, (p, q, k), f32),
        _spec(one_chip, (p, q, k, k), f32)).compile()


def test_gateway_decode_step_compiles_at_two_layers(one_chip):
    cfg = dataclasses.replace(QWEN, n_layers=2)
    _, n_periods = period_plan(cfg)
    s_max = PAGES_PER_SLOT * PAGE
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                          params)
    view = _spec(one_chip, (n_periods, SLOTS, s_max, HKV, DH), jnp.bfloat16)
    batch = {"token": _spec(one_chip, (SLOTS, 1), jnp.int32),
             "lens": _spec(one_chip, (SLOTS,), jnp.int32)}
    compiled = jax.jit(build_gateway_step(cfg)).lower(
        params, {"pos0": {"k": view, "v": view}}, batch).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
