"""Batched serving driver: greedy decode against a KV cache.

Runnable on this CPU container with smoke configs::

    PYTHONPATH=src python -m repro.launch.serve --arch smoke:qwen3-4b \
        --batch 4 --prompt-len 16 --gen 32

With ``--fleet N`` the decode loop is dispatched through the closed-loop
photonic runtime (``repro.runtime``): N virtual chip instances with
independent device realizations back the serving plane, health probes
run out-of-band, and (with ``--drift``) thermal phase drift degrades
chips until the router schedules recalibration around live traffic.
With ``--fleet-tenants T`` every chip is time-multiplexed across T
mapped layers (per-layer Σ banks), and each decode step's PTC traffic
is routed to a (chip, tenant) slot — step ``i`` exercises tenant
``i mod T``, the round-robin a T-layer model would drive — so a single
drifted layer triggers *partial* recalibration of its own blocks only.
In this mode the LM math itself stays on the digital twin; the fleet
models the photonic boards' device state, health, and routing.

``--hw-logits`` goes the rest of the way: the served model's own PTC
layers deploy onto the fleet chips (one tenant per layer, via
``core.mapping.parallel_map(block_range=)``), each decode step routes
the *whole forward pass* to one chip, and every PTC matmul executes
through ``driver.forward_layer`` against that chip's realized
(drifted!) transfer — the logits ARE what the photonic hardware
computes, so accuracy-vs-drift is measurable end to end
(``benchmarks/e2e_accuracy.py``).  Sibling projections sharing one
input (q/k/v, gate/up) ship as one v3 ``batch`` frame.  ``--hw-shadow``
deploys identically but applies the deployment-time readback transfer
digitally — the twin-path reference that is token-identical to
``--hw-logits`` at σ_drift = 0 (a conformance gate across all three
driver transports).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..compile_cache import enable_compile_cache
from ..data import lm_batch
from ..models.lm import (ArchConfig, init_model, init_decode_cache,
                         build_serve_step)
from .steps import greedy_decode
from .train import parse_arch


def add_autopilot_args(ap: argparse.ArgumentParser) -> None:
    """Fleet scheduling/routing knobs shared by ``launch.serve`` and
    ``serving.gateway`` (both build their fleet via
    :func:`_hw_runtime_config`)."""
    ap.add_argument("--autopilot", action="store_true",
                    help="forecast-driven fleet maintenance: proactive "
                         "recals before predicted alarm crossings, "
                         "degradation-rate repair priority, trough-"
                         "scheduled via the gateway's occupancy signal")
    ap.add_argument("--ap-horizon", type=int, default=40,
                    help="autopilot: proactive window (ticks)")
    ap.add_argument("--ap-trough", type=float, default=0.5,
                    help="autopilot: load forecast at/below this "
                         "fraction of capacity counts as a trough")
    ap.add_argument("--ap-budget", type=float, default=None,
                    help="autopilot: recal PTC-call envelope per window "
                         "(default unlimited)")
    ap.add_argument("--ap-window", type=int, default=200,
                    help="autopilot: budget window (ticks)")
    ap.add_argument("--fleet-policy", default=None,
                    choices=["drift_aware", "accuracy_aware",
                             "least_served"],
                    help="dispatch ranking policy (default: the demo "
                         "config's drift_aware)")


def _apply_fleet_policy(args, cfg):
    """Fold the shared CLI scheduling knobs into a RuntimeConfig."""
    policy = getattr(args, "fleet_policy", None)
    if policy:
        cfg = dataclasses.replace(cfg, router_policy=policy)
    if getattr(args, "autopilot", False):
        from ..runtime.autopilot import AutopilotConfig
        budget = getattr(args, "ap_budget", None)
        cfg = dataclasses.replace(cfg, autopilot=AutopilotConfig(
            horizon=getattr(args, "ap_horizon", 40),
            trough_load=getattr(args, "ap_trough", 0.5),
            budget_calls=float("inf") if budget is None else budget,
            budget_window=getattr(args, "ap_window", 200)))
    return cfg


def _build_fleet(args):
    from ..runtime.demo import default_runtime_config, _make_weights
    from ..runtime.fleet import make_fleet, make_router

    sigma = args.drift_sigma if args.drift else 0.0
    cfg = default_runtime_config(k=args.fleet_k, sigma_drift=sigma,
                                 probe_every=args.probe_every,
                                 driver_kind=args.fleet_driver)
    cfg = _apply_fleet_policy(args, cfg)
    kw, kf = jax.random.split(jax.random.PRNGKey(args.seed + 17))
    dim = args.fleet_dim
    tenants = max(1, args.fleet_tenants)
    weights = _make_weights(kw, dim, tenants)
    chips = make_fleet(kf, args.fleet,
                       weights if tenants > 1 else weights[0], cfg)
    return make_router(chips, cfg, seed=args.seed), dim, tenants


def _hw_runtime_config(args):
    """Fleet policy for the hw-logits plane: explicit override via
    ``args.runtime_cfg`` (the accuracy benchmark tunes thresholds), else
    the demo defaults at the CLI-selected drift/probe cadence, with the
    shared scheduling knobs (--autopilot, --fleet-policy) folded in."""
    from ..runtime.demo import default_runtime_config

    cfg = getattr(args, "runtime_cfg", None)
    if cfg is None:
        sigma = args.drift_sigma if args.drift else 0.0
        cfg = default_runtime_config(k=args.fleet_k, sigma_drift=sigma,
                                     probe_every=args.probe_every,
                                     driver_kind=args.fleet_driver)
        cfg = _apply_fleet_policy(args, cfg)
    if getattr(args, "deploy_zo", False):
        cfg = dataclasses.replace(cfg, deploy_zo=True)
    return cfg


def _build_hw_plane(args, cfg, params, serve_fn, extras, mode: str):
    """Enumerate the model's decode-path PTC layers (one dry digital
    step) and deploy them — one tenant per layer — onto a fresh fleet."""
    from ..runtime.hw_serve import record_ptc_layers, HwServePlane

    cache0 = init_decode_cache(cfg, args.batch, 2)
    batch0 = {"token": jnp.zeros((args.batch, 1), jnp.int32),
              "cache_len": jnp.asarray(0, jnp.int32), **extras}
    layers = record_ptc_layers(serve_fn, params, cache0, batch0)
    kf = jax.random.split(jax.random.PRNGKey(args.seed + 17))[1]
    return HwServePlane(kf, layers, _hw_runtime_config(args), args.fleet,
                        mode=mode, seed=args.seed,
                        recal_enabled=not getattr(args, "no_recal", False))


def run(args) -> dict:
    """Serve ``args.gen`` tokens (optionally through the fleet runtime)
    and return the outcome: generated tokens, per-step argmax
    predictions, plus the router's report — the seeded-regression
    surface the e2e tests lock down.

    With ``--gateway`` the whole run is delegated to the continuous-
    batching gateway (``repro.serving``): the workload becomes an
    open-loop request stream instead of one lockstep batch, and the
    returned dict is the gateway report."""
    if getattr(args, "gateway", False):
        from ..serving.gateway import run as run_gateway
        return run_gateway(args)
    cfg = (args.arch if isinstance(args.arch, ArchConfig)
           else parse_arch(args.arch))
    hw_mode = None
    if getattr(args, "hw_logits", False):
        hw_mode = "route"
    if getattr(args, "hw_shadow", False):
        if hw_mode is not None:
            raise ValueError("--hw-logits and --hw-shadow are exclusive")
        hw_mode = "shadow"
    if hw_mode is not None:
        if args.fleet <= 0:
            raise ValueError("--hw-logits/--hw-shadow need --fleet N chips")
        if cfg.n_experts > 0:
            # expert FFNs execute under jax.vmap, where the layer hook
            # is structurally inert (tracer guard) — serving them would
            # silently leave the dominant FFN compute digital while
            # claiming hardware logits.  Refuse until stacked-factor
            # tenants land (ROADMAP: hw-logits for MoE experts).
            raise ValueError(
                f"--hw-logits/--hw-shadow do not support MoE archs yet "
                f"({cfg.name}: {cfg.n_experts} experts run under vmap, "
                f"unreachable by the PTC execution hook)")
        # the layer-execution hook needs concrete activations: run the
        # decode body as an unjitted python loop over periods
        cfg = dataclasses.replace(cfg, unroll=True, remat=False)

    params = getattr(args, "params_override", None)
    if params is None:
        params = init_model(jax.random.PRNGKey(args.seed), cfg)

    prompt = getattr(args, "prompt_tokens", None)
    if prompt is None:
        prompt = lm_batch(args.seed, 0, args.batch, args.prompt_len,
                          cfg.vocab)["tokens"]
    else:
        prompt = np.asarray(prompt, np.int32)
    prompt_len = int(prompt.shape[1])
    max_len = prompt_len + args.gen
    cache = init_decode_cache(cfg, args.batch, max_len)
    serve_fn = build_serve_step(cfg)
    serve = serve_fn if hw_mode is not None else jax.jit(serve_fn)

    extras = {}
    if cfg.family == "vlm":
        extras["img"] = 0.1 * jnp.ones(
            (args.batch, cfg.n_img_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        extras["enc_out"] = 0.1 * jnp.ones(
            (args.batch, prompt_len, cfg.d_model), jnp.float32)

    on_step = None
    router = None
    plane = None
    report = None
    if hw_mode is not None:
        plane = _build_hw_plane(args, cfg, params, serve_fn, extras, hw_mode)
    elif args.fleet > 0:
        router, fleet_dim, tenants = _build_fleet(args)
        kx = jax.random.PRNGKey(args.seed + 23)

        def on_step(i):
            # every serve-path step (prefill included) runs on one
            # routed (drifted) board, on the step's (chip, tenant) slot
            x = jax.random.normal(jax.random.fold_in(kx, i),
                                  (args.batch, fleet_dim))
            router.serve(x, tenant=i % tenants)
            router.tick()

    preds: list = []
    logits_trace: list | None = \
        [] if getattr(args, "trace_logits", False) else None
    try:
        t0 = time.time()
        gen, cache = greedy_decode(serve, params, cache, prompt, args.gen,
                                   extras=extras, on_step=on_step,
                                   layer_exec=plane, preds_out=preds,
                                   logits_out=logits_trace)
        dt = time.time() - t0
        if plane is not None:
            report = plane.report()
        elif router is not None:
            report = router.report()
    finally:
        if plane is not None:
            plane.close()
        if router is not None:
            router.close()
    out = dict(gen=np.asarray(gen), wall_s=dt, report=report,
               preds=np.stack(preds, axis=1) if preds else
               np.zeros((args.batch, 0), np.int32))
    if logits_trace is not None:
        out["logits"] = np.stack(logits_trace, axis=0)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fleet", type=int, default=0,
                    help="route decode steps through N virtual chips")
    ap.add_argument("--drift", action="store_true",
                    help="enable thermal phase drift on the fleet")
    ap.add_argument("--drift-sigma", type=float, default=0.015)
    ap.add_argument("--probe-every", type=int, default=10)
    ap.add_argument("--fleet-k", type=int, default=6)
    ap.add_argument("--fleet-dim", type=int, default=18)
    ap.add_argument("--fleet-tenants", type=int, default=1,
                    help="mapped layers time-sharing each chip; decode "
                         "step i routes to tenant i %% T (synthetic-"
                         "traffic mode; --hw-logits derives tenants from "
                         "the model instead)")
    ap.add_argument("--fleet-driver", default="twin",
                    choices=["twin", "subprocess", "socket"],
                    help="photonic device transport behind the fleet")
    ap.add_argument("--hw-logits", action="store_true",
                    help="deploy the model's PTC layers onto the fleet "
                         "(one tenant per layer) and execute every "
                         "decode-path matmul through the routed chip's "
                         "realized transfer — logits come from the "
                         "(drifting) hardware, not the digital twin")
    ap.add_argument("--hw-shadow", action="store_true",
                    help="deploy like --hw-logits but serve from the "
                         "deployment-time readback transfer digitally "
                         "(the σ=0 token-identity reference path)")
    ap.add_argument("--deploy-zo", action="store_true",
                    help="run PM's alternate-ZCD stage at deployment "
                         "(lower mapping floor for accuracy studies)")
    ap.add_argument("--no-recal", action="store_true",
                    help="open loop: alarms fire, nothing recovers")
    add_autopilot_args(ap)
    ap.add_argument("--gateway", action="store_true",
                    help="serve an open-loop request stream through the "
                         "continuous-batching gateway (repro.serving) "
                         "instead of one lockstep batch; --gw-* flags "
                         "configure it")
    from ..serving.gateway import add_gateway_args
    add_gateway_args(ap)
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.gateway:
        rep = run(args)
        c = rep["config"]
        lat = rep["latency_steps"]
        print(f"gateway [{c['hw_mode']}] {c['arch']}: {c['n_requests']} "
              f"requests, {rep['tokens_out']} tokens in "
              f"{rep['wall_s']:.1f}s ({rep['tokens_per_s']:.1f} tok/s), "
              f"latency p50={lat['p50']:.0f} p99={lat['p99']:.0f} steps")
        return 0

    out = run(args)
    gen = out["gen"]
    print(f"generated {gen.shape} tokens in {out['wall_s']:.1f}s "
          f"({gen.size / out['wall_s']:.1f} tok/s)")
    print("sample:", gen[0][:24])

    rep = out["report"]
    if rep is not None:
        alarms = sum(c["alarms"] for c in rep["chips"])
        recals = sum(c["recals"] for c in rep["chips"])
        n_tenants = len(rep["chips"][0]["tenants"])
        print(f"fleet: {args.fleet} chips x {n_tenants} "
              f"tenant(s), {rep['ticks']} ticks, "
              f"{rep['dropped']} dropped, {alarms} alarms, "
              f"{recals} recals")
        hw = rep.get("hw")
        if hw is not None:
            print(f"hw-logits [{hw['mode']}]: {len(hw['layers'])} PTC "
                  f"layers as tenants, {hw['frames']} driver frames over "
                  f"{hw['steps']} steps "
                  f"({hw['frames_per_step']:.1f} frames/step), "
                  f"{hw['hw_calls']} hw matmuls, "
                  f"{hw['shadow_calls']} shadow matmuls, "
                  f"{hw['dropped_passes']} dropped passes")
        for c in rep["chips"]:
            print(f"  chip {c['chip']}: {c['status']:<13} "
                  f"served={c['served']:4d} d̂={c['distance']:.4f} "
                  f"alarms={c['alarms']} recals={c['recals']}")
            if n_tenants > 1:
                for t in c["tenants"]:
                    print(f"    tenant {t['tenant']} "
                          f"blocks{t['block_range']}: "
                          f"served={t['served']:4d} d̂={t['distance']:.4f} "
                          f"alarms={t['alarms']} recals={t['recals']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
