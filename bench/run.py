#!/usr/bin/env python3
"""Run one benchmark cell once and print one JSON result line.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run it from the checkout root on a machine with the chips the cell asks
for.  ``<name>`` is a workload of ``BENCHMARK.json``; its configuration,
traffic mix, entry, reference and metric readers are found by name under
``bench/`` (``bench/cell.py``).  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics
read from a profiler trace of the window.  The last lines on standard
error, and the result's last key ``checks``, give each number that
decides ``correct`` beside its limit.

With no TPU, or fewer chips than the cell asks for, it exits 2 and
prints no result.  Compiled programs are kept in ``<checkout>/.jax_cache``
(or where ``JAX_COMPILATION_CACHE_DIR`` says), so only a checkout's
first run of a cell compiles.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

from bench.cell import find_cell, load_entry  # noqa: E402


class NoChip(RuntimeError):
    pass


def require_chips(n: int) -> list:
    """The devices of the cell, or NoChip: the benchmark measures a TPU
    and never falls back to another backend."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devices[0].platform!r})")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def enable_cache() -> str:
    import jax

    from repro.compile_cache import enable_compile_cache

    where = enable_compile_cache()
    # keep every program, small ones too: each is loaded, not compiled,
    # by the next run in this checkout
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # key entries on the HLO metadata too: a step program cached without
    # the model's named scopes would otherwise be loaded for one with
    # them, and the scope map (engine_trace.step_scopes) would find none
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    return where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = find_cell(args.workload)
    try:
        devices = require_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    marks = {"start": T_START, "devices": time.perf_counter()}
    enable_cache()
    seed = args.seed % 2 ** 64      # any whole number; generators want >= 0
    result = load_entry(cell.mix["entry"]).run(
        cell, seed, args.seconds, bool(args.trace), marks, devices)
    checks = result.pop("checks")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device", "breakdown")
            if k in result}
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
