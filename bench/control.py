#!/usr/bin/env python3
"""Readings that set a serving cell's limit: the program's and the control's.

    python bench/control.py --workload <name> --seeds <n> [<n> ...]
        [--seconds <s>] [--fault <name>]

Run it from the checkout root on the chip, at the cell's own size.  For
each seed it draws the benchmark's weights, serves the cell's queue
through the gateway for a window of ``--seconds`` (the benchmark's
``run_seconds`` unless given) exactly as a run does, and judges two
readings over the run's own sample of finished requests with the run's
own ``judge``:

* program: the widest gap by which a served token's float32 reference
  logit lies below the reference's best, in units of the position's
  logit spread (what every run compares with the limit);
* control: the same gap for the token that the reference computed with
  fp8 (e4m3, one scale per tensor) puts first at each of those
  positions, the precision below the bfloat16 the configuration states.
  Its ``correct`` has to come out false.

With ``--fault`` (``bench/faults.py``) the fault is planted in the
program first, and the program's ``correct`` has to come out false.

The lower reading of the limit is the largest sound program gap over a
dozen seeds or more, the upper the smallest control gap; one line per
seed and a summary are printed.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(CHECKOUT), str(CHECKOUT / "src")]


def readings(cell, seed: int, seconds: float) -> dict:
    """One seed: serve a window, then judge the program and the control
    over the run's sample."""
    from bench.arch import program_arch
    from bench.entries import gateway

    cfg, mix = cell.config, cell.mix
    limit = mix["check"]["limit_gap_sigma"]
    arch = program_arch(cfg)
    params = gateway.make_params(cfg, arch, seed)
    gw = gateway.build_gateway(arch, params, mix, seed)
    win = gateway.Window()
    reqs = gateway.queue_requests(mix, seed, arch.vocab, win)
    t0, t1 = gateway.serve(gw, reqs, win, seconds)
    del gw
    gc.collect()
    picked = gateway.sample(reqs, seed, mix["check"])
    n_failed = sum(gateway.failed(r) for r in reqs)
    t = time.perf_counter()
    prog = gateway.reference_gaps(params, cfg, mix, picked)
    ref_s = time.perf_counter() - t
    ctrl = gateway.reference_gaps(params, cfg, mix, picked, control=True)
    prog_ok, prog_checks = gateway.judge(prog, n_failed, limit)
    ctrl_ok, ctrl_checks = gateway.judge(ctrl, n_failed, limit)
    return {"seed": seed, "window_s": t1 - t0, "reference_s": ref_s,
            "requests": len(picked), "tokens": int(prog.size),
            "program_correct": prog_ok,
            "program_max_gap": prog_checks["max_gap_sigma"]["value"],
            "program_nonzero": int((prog > 0).sum()),
            "failed": n_failed,
            "control_correct": ctrl_ok,
            "control_max_gap": ctrl_checks["max_gap_sigma"]["value"],
            "control_nonzero": int((ctrl > 0).sum())}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--fault")
    args = ap.parse_args(argv)

    from bench.cell import find_cell
    from bench.faults import plant
    from bench.run import enable_cache, require_chips

    cell = find_cell(args.workload)
    seconds = args.seconds or json.loads(
        (CHECKOUT / "BENCHMARK.json").read_text())["run_seconds"]
    require_chips(cell.chips)
    enable_cache()
    if args.fault:
        plant(args.fault)
    rows = []
    for seed in args.seeds:
        rows.append(readings(cell, seed % 2 ** 64, seconds))
        print(json.dumps(rows[-1]), flush=True)
    lower = max(r["program_max_gap"] for r in rows)
    upper = min(r["control_max_gap"] for r in rows)
    print(json.dumps({"workload": args.workload, "fault": args.fault,
                      "seeds": len(rows), "lower": lower, "upper": upper,
                      "upper_over_lower": upper / lower if lower else None,
                      "limit": cell.mix["check"]["limit_gap_sigma"],
                      "program_correct": [r["program_correct"] for r in rows],
                      "control_correct": [r["control_correct"] for r in rows]}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
