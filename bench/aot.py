#!/usr/bin/env python3
"""Compile a serving cell's programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python bench/aot.py --workload <name>

Compiles, at the cell's sizes, the gateway step (or the chunked prefill
step), the paged gather and the paged scatter, with the Pallas kernels
lowered for Mosaic, and prints each program's ``memory_analysis()`` and
the cell's reckoned device memory: parameters, the KV pools, one step's
gathered views (the engine drops a step's views before the next
gather, and its scatter writes into the donated pool, with no copy) and
the largest program's temporaries.  The pools' rows are those of a
gateway built at one page (``bench.arch.kv_pool``), as the
benchmark's readers take them.  A compile that passes is not a chip run
and says nothing of times.
"""

from __future__ import annotations

import argparse
import os
import sys
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GIB = 2 ** 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.cell import find_cell
    from bench.arch import kv_pool, program_arch
    from repro.kernels import ops, paged_kv
    from repro.models.lm import (build_gateway_prefill_step,
                                 build_gateway_step, init_model)
    from repro.serving.engine import GatewayConfig, ServingGateway
    from repro.serving.kv_pages import PageConfig

    jax.config.update("jax_enable_compilation_cache", False)
    ops.default_interpret = lambda: False       # lower Pallas for Mosaic
    cell = find_cell(args.workload)
    cfg, mix = cell.config, cell.mix
    arch = program_arch(cfg)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = jax.tree.map(lambda a: sds(a.shape, a.dtype), jax.eval_shape(
        lambda k: init_model(k, arch), jax.random.PRNGKey(0)))
    b, c = mix["slots"], mix["prefill_chunk"]
    ps, j = mix["page_size"], mix["max_pages_per_slot"]
    one_page = ServingGateway(arch, params, GatewayConfig(
        slots=1, pages=PageConfig(page_size=ps, n_pages=1,
                                  max_pages_per_slot=1)))
    layers, rows = one_page.n_periods, kv_pool(one_page)
    del one_page
    pools = {name: {kk: sds((layers * (mix["n_pages"] + 1), ps) + row, dt)
                    for kk, (row, dt) in t.items()}
             for name, t in rows.items()}
    views = {name: {kk: sds((layers, b, j * ps) + row, dt)
                    for kk, (row, dt) in t.items()}
             for name, t in rows.items()}
    batch = {"token": sds((b, c), jnp.int32), "lens": sds((b,), jnp.int32)}
    if c > 1:
        batch["n_valid"] = sds((b,), jnp.int32)
        step = build_gateway_prefill_step(arch)
    else:
        step = build_gateway_step(arch)
    progs = {"step": jax.jit(step).lower(params, views, batch)}
    for name, t in pools.items():
        for kk, pool in t.items():
            progs[f"gather {name}.{kk}"] = paged_kv.paged_gather.lower(
                sds((layers * b, j), jnp.int32), pool, lead=(layers, b),
                interpret=False)
            progs[f"scatter {name}.{kk}"] = paged_kv.paged_scatter.lower(
                sds((layers * b * c, 2), jnp.int32),
                sds((layers * b * c,) + pool.shape[2:], pool.dtype), pool,
                interpret=False)
    temps = {}
    for name, lowered in progs.items():
        m = lowered.compile().memory_analysis()
        temps[name] = m.temp_size_in_bytes
        print(f"{name}: arguments {m.argument_size_in_bytes / GIB:.3f} GiB, "
              f"outputs {m.output_size_in_bytes / GIB:.3f} GiB, aliased "
              f"{m.alias_size_in_bytes / GIB:.3f} GiB, temporaries "
              f"{m.temp_size_in_bytes / GIB:.3f} GiB")
    def nbytes(tree):
        return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))

    p_bytes, pool_bytes, view_bytes = map(nbytes, (params, pools, views))
    total = p_bytes + pool_bytes + view_bytes + max(temps.values())
    print(f"reckoned device memory: parameters {p_bytes / GIB:.3f} GiB + "
          f"pools {pool_bytes / GIB:.3f} GiB + one step's views "
          f"{view_bytes / GIB:.3f} GiB + the largest temporaries "
          f"{max(temps.values()) / GIB:.3f} GiB = {total / GIB:.3f} GiB "
          f"of the chip's 16 GiB")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
