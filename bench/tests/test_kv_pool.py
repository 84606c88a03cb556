"""The paged KV readers take a row's bytes from the pools the gateway
built: today's bytes in both cells, a latent row where the pool holds
one, and no reading where the pool's tensors differ."""

import json
import types

import jax
import jax.numpy as jnp
import pytest

from bench import flops, tracing
from bench.arch import kv_pool, program_arch
from bench.cell import find_cell, load_metric
from bench.entries import gateway
from bench.tests.test_correct import DATA

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
# one call of each kernel, 1 ms of device time, in a window of 10 ms
TRACE = tracing.Trace(
    ops={"/device:TPU:0": [["jit_paged_gather/paged_gather.1", 0, 10 ** 6],
                           ["jit_paged_scatter/paged_scatter.2", 2 * 10 ** 6,
                            10 ** 6]]},
    modules={}, spans=[])


def engine(layers: int, pools: dict):
    """What ``kv_pool`` reads of a gateway: its pools, 3 pages a layer."""
    return types.SimpleNamespace(n_periods=layers, _pools={
        name: {kk: jax.ShapeDtypeStruct((layers * 3, 16) + row, jnp.bfloat16)
               for kk, row in t.items()}
        for name, t in pools.items()})


def readings(mix: dict, gw) -> tuple:
    ctx = gateway.MetricContext(
        cfg={}, mix=mix, peaks=PEAKS, trace=TRACE, lo=0, hi=10 ** 7,
        window_s=0.01, counts={
            "kv_pool": kv_pool(gw),
            "kv_layers": gw.n_periods})
    return tuple(load_metric(name).read(ctx) for name in
                 ("paged_gather_roofline", "paged_scatter_roofline"))


def share(nbytes: float) -> float:
    return 100.0 * nbytes / PEAKS["hbm_bytes_s"] / 1e-3


@pytest.mark.parametrize("cell", ["qwen3-4b.lmsys-chat",
                                  "olmo-1b.azure-conv"])
def test_both_cells_read_todays_bytes(cell):
    c = find_cell(cell)
    cfg, mix = c.config, c.mix
    layers, hkv, dh = (cfg["num_hidden_layers"], cfg["num_key_value_heads"],
                       cfg["head_dim"])
    gw = engine(layers, {"pos0": {"k": (hkv, dh), "v": (hkv, dh)}})
    row = hkv * dh * 2
    assert row == {"qwen3-4b": 2048, "olmo-1b": 4096}[cfg["arch"]]
    rows = layers * mix["slots"]
    gather = 2 * rows * mix["max_pages_per_slot"] * mix["page_size"] * row
    scatter = 2 * rows * mix["prefill_chunk"] * row
    assert readings(mix, gw) == pytest.approx((share(gather),
                                               share(scatter)), rel=1e-12)


def test_a_latent_row_is_its_own_bytes():
    mix = find_cell("qwen3-4b.lmsys-chat").mix
    gw = engine(9, {"pos0": {"c": (1, 576)}})
    assert flops.pool_row_bytes(kv_pool(gw)) == [1152]
    rows = 9 * mix["slots"]
    assert readings(mix, gw) == pytest.approx((
        share(2 * rows * mix["max_pages_per_slot"] * mix["page_size"] * 1152),
        share(2 * rows * 1152)), rel=1e-12)


def test_tensors_of_different_rows_read_nothing():
    mix = find_cell("qwen3-4b.lmsys-chat").mix
    gw = engine(9, {"pos0": {"c": (1, 512), "r": (1, 64)}})
    assert readings(mix, gw) == (None, None)


def test_kv_pool_reads_the_engines_pools():
    """On a gateway the program builds: one k and one v tensor of rows
    (kv heads, head dim) in bfloat16, a stripe of pages per layer."""
    from repro.serving.engine import GatewayConfig, ServingGateway
    from repro.serving.kv_pages import PageConfig

    cfg = json.loads((DATA / "tiny-qwen.json").read_text())
    arch = program_arch(cfg)
    gw = ServingGateway(arch, {}, GatewayConfig(
        slots=2, pages=PageConfig(page_size=4, n_pages=3,
                                  max_pages_per_slot=2)))
    row = (cfg["num_key_value_heads"], cfg["head_dim"])
    assert kv_pool(gw) == {"pos0": {"k": (row, jnp.bfloat16),
                                            "v": (row, jnp.bfloat16)}}
    assert gw.n_periods == cfg["num_hidden_layers"]
    assert flops.pool_row_bytes(kv_pool(gw)) == [2 * 16 * 2] * 2
