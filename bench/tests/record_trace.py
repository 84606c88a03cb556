#!/usr/bin/env python3
"""Record the small trace that the reduction's tests read (``data/``).

    python bench/tests/record_trace.py --out <dir>

On one TPU: olmo-1b at the ``olmo-1b.azure-conv`` cell's geometry (12
slots, prefill chunk 32, S_max 2048), weights from seed 0, serves 12
requests of 40 prompt tokens and 3 new, which takes four chunked-prefill
gateway steps, traced inside one ``bench.round`` span.  Writes
``prompt-heavy-4steps.xplane.pb.gz`` (the profiler's trace) and
``prompt-heavy-4steps.context.json.gz``: the gateway's counters across
the span, the step program's scope map (``engine_trace.step_scopes``),
the parameters' PTC leaves and the pool's rows, which a reader's
context holds in a run.
"""

from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]

CELL = "olmo-1b.azure-conv"
NAME = "prompt-heavy-4steps"
REQUESTS, PROMPT, NEW = 12, 40, 3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import engine_trace, tracing
    from bench.arch import kv_pool, program_arch
    from bench.cell import find_cell
    from bench.entries.gateway import (build_gateway, counts_across,
                                       engine_counters, make_params)
    from bench.run import enable_cache
    from repro.serving.scheduler import Request

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    enable_cache()
    cell = find_cell(CELL)
    cfg, mix = cell.config, cell.mix
    arch = program_arch(cfg)
    params = make_params(cfg, arch, 0)
    gw = build_gateway(arch, params, mix, 0)
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, max_new=NEW, prompt=rng.integers(
        0, arch.vocab, PROMPT).astype(np.int32)) for i in range(REQUESTS)]
    trace_dir = tempfile.mkdtemp(prefix="record-trace-")
    before = engine_counters(gw)
    tracing.start(trace_dir)
    with jax.profiler.TraceAnnotation("bench.round"):
        gw.run(reqs)
    tracing.stop()
    counts = counts_across(before, engine_counters(gw))
    pool, n_periods = kv_pool(gw), gw.n_periods
    scopes = engine_trace.step_scopes(gw)
    del gw
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    pb = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True), key=os.path.getmtime)[-1]
    with open(pb, "rb") as src, \
            gzip.open(out / f"{NAME}.xplane.pb.gz", "wb") as dst:
        shutil.copyfileobj(src, dst)
    shutil.rmtree(trace_dir)
    context = {"cell": CELL, "requests": REQUESTS, "prompt_len": PROMPT,
               "new_tokens": NEW, "counts": counts,
               "kv_pool": {name: {kk: [list(row), str(dtype)]
                                  for kk, (row, dtype) in t.items()}
                           for name, t in pool.items()},
               "kv_layers": n_periods,
               "ptc_leaves": sorted(engine_trace.ptc_leaves(params)),
               "scopes": scopes}
    with gzip.open(out / f"{NAME}.context.json.gz", "wt") as f:
        json.dump(context, f, sort_keys=True)
    print(json.dumps({"counts": counts, "scopes": len(scopes),
                      "ptc_leaves": context["ptc_leaves"]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
