"""The reduction of what the serving engine itself tells the profiler.

``ServingGateway.run`` wraps each busy step in a ``gateway.step`` span
holding one span per phase (``gateway.admit``, ``stage``, ``gather``,
``forward``, ``scatter``, ``fetch``, ``emit``), and the model's named
scopes (``s{i}.attn``, ``s{i}.mlp`` and the PTC leaves ``wq wk wv wo
gate up down``) reach each op's HLO ``op_name``.  These functions read
both from a ``tracing.Trace`` whose ``spans`` hold the engine's spans
beside the harness's, and from a map of the step program's instructions
to their ``op_name`` (``op_scopes``), built after the window by
``step_scopes``.  Like ``bench/tracing.py`` they are arithmetic, tested
on made-up traces.
"""

from __future__ import annotations

import re

from . import tracing

__all__ = ["PHASES", "step_self_ms", "phase_ms",
           "idle_placed", "op_scopes", "layer_of", "layer_seconds",
           "layer_ms", "step_scopes"]

STEP = "gateway.step"
PHASES = ("gateway.admit", "gateway.stage", "gateway.gather",
          "gateway.forward", "gateway.scatter", "gateway.fetch",
          "gateway.emit")
FETCH = "gateway.fetch"
LEAVES = frozenset(("wq", "wk", "wv", "wo", "gate", "up", "down"))
ATTN = re.compile(r"^s\d+\.attn$")
STEP_PROGRAM = r"^jit_(gateway_step|prefill_step)\b"

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")


def _inside(spans, name: str, lo: float, hi: float):
    return [s for s in spans
            if s[0] == name and s[1] >= lo and s[1] + s[2] <= hi]


def step_self_ms(trace: tracing.Trace, lo: float, hi: float) -> list:
    """Per engine step inside [lo, hi], ms: the ``gateway.step`` span
    less its ``gateway.fetch`` child.  Besides the host's own work this
    holds any wait at dispatch (a TPU v5e trace shows the host blocked
    in ``gateway.scatter`` while the device runs), so it bounds, and
    does not equal, the host time the device waits on."""
    fetches = _inside(trace.spans, FETCH, lo, hi)
    out = []
    for _, start, dur in _inside(trace.spans, STEP, lo, hi):
        waited = sum(d for _, s, d in fetches
                     if s >= start and s + d <= start + dur)
        out.append((dur - waited) / 1e6)
    return out


def phase_ms(trace: tracing.Trace, lo: float, hi: float) -> dict:
    """{phase: host ms per engine step} over the steps inside [lo, hi]."""
    n = len(_inside(trace.spans, STEP, lo, hi))
    if not n:
        return {}
    return {p: sum(d for _, _, d in _inside(trace.spans, p, lo, hi))
            / n / 1e6 for p in PHASES}


def idle_placed(trace: tracing.Trace, lo: float, hi: float):
    """Share (%) of the first device's idle time in [lo, hi] whose gap
    midpoint lies in an engine phase span; None with no idle time."""
    gaps = tracing.idle_gaps(trace, lo, hi, n=None)
    idle = sum(s for _, s in gaps)
    if idle <= 0:
        return None
    placed = sum(s for name, s in gaps if name in PHASES)
    return 100.0 * placed / idle


def op_scopes(hlo_text: str, program: str) -> dict:
    """``{"jit_<program>/<instruction>": op_name}`` for every instruction
    of a compiled module's HLO text, named as the trace names its ops.
    A fusion takes the ``op_name`` of its computation's root (its own
    where the root has none); an instruction with none maps to ""."""
    own, calls, roots = {}, {}, {}
    comp = None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = op.group(1).replace('\\"', '"') if op else ""
        called = _CALLS.search(line)
        if called:
            calls[name] = called.group(1)
        if m.group(1) and comp is not None:
            roots[comp] = name

    def resolve(name: str, depth: int = 0) -> str:
        root = roots.get(calls.get(name))
        if root is None or depth > 8:
            return own[name]
        return resolve(root, depth + 1) or own[name]

    return {f"jit_{program}/{name}": resolve(name) for name in own}


def layer_of(op_name: str) -> str | None:
    """``"ptc"`` for an op under a PTC leaf scope, ``"attn"`` for one
    under an ``s{i}.attn`` scope and no leaf, else None."""
    parts = op_name.split("/")
    if LEAVES.intersection(parts):
        return "ptc"
    if any(ATTN.match(p) for p in parts):
        return "attn"
    return None


def layer_seconds(trace: tracing.Trace, scopes: dict, lo: float, hi: float
                  ) -> tuple[dict, int]:
    """({"attn": s, "ptc": s, "other": s}, ops absent from ``scopes``):
    device seconds of the step program's ops in [lo, hi] by layer,
    summed over the device planes; loops and calls are left out, as
    their time is that of the ops they hold."""
    out = {"attn": 0.0, "ptc": 0.0, "other": 0.0}
    absent = 0
    step_op = re.compile(STEP_PROGRAM + "/")
    for evs in trace.ops.values():
        for name, s, e in tracing._clip(evs, lo, hi):
            if not step_op.match(name) or tracing.CONTAINER.search(name):
                continue
            if name not in scopes:
                absent += 1
                continue
            out[layer_of(scopes[name]) or "other"] += (e - s) / 1e9
    return out, absent


def layer_ms(ctx, layer: str):
    """Device ms of ``layer`` (``"attn"`` or ``"ptc"``) per run of the
    step program in a reader's context; None without a scope map
    (``ctx.scopes``) in which some op carries a layer scope."""
    scopes = getattr(ctx, "scopes", None)
    if not scopes or not any(map(layer_of, scopes.values())):
        return None
    _, n = tracing.module_runs(ctx.trace, STEP_PROGRAM, ctx.lo, ctx.hi)
    if not n:
        return None
    seconds, _ = layer_seconds(ctx.trace, scopes, ctx.lo, ctx.hi)
    return 1e3 * seconds[layer] / n


def step_scopes(arch, params, mix: dict) -> dict:
    """The scope map of a serving cell's step program, lowered at the
    cell's fixed shapes as the engine calls it (the one-token
    ``gateway_step``, or ``prefill_step`` at a prefill chunk over 1) and
    compiled, which loads the program from the persistent cache where
    the run compiled it.  Call it after the window: it traces the
    model."""
    import jax
    import jax.numpy as jnp

    from repro.models.lm import (build_gateway_prefill_step,
                                 build_gateway_step, period_plan)

    dev = next(iter(jax.tree.leaves(params)[0].devices()))
    sharding = jax.sharding.SingleDeviceSharding(dev)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    plan, n_periods = period_plan(arch)
    b, c = mix["slots"], mix["prefill_chunk"]
    s_max = mix["max_pages_per_slot"] * mix["page_size"]
    views = {}
    for i, sub in enumerate(plan):
        if sub.kind != "attn":
            raise ValueError("serving cells run attention-only archs")
        acfg = arch.attn_cfg(sub.window)
        view = sds((n_periods, b, s_max, acfg.n_kv_heads, acfg.head_dim),
                   jnp.bfloat16)
        views[f"pos{i}"] = {"k": view, "v": view}
    batch = {"token": sds((b, c), jnp.int32), "lens": sds((b,), jnp.int32)}
    if c > 1:
        batch["n_valid"] = sds((b,), jnp.int32)
        step, program = build_gateway_prefill_step(arch), "prefill_step"
    else:
        step, program = build_gateway_step(arch), "gateway_step"
    text = jax.jit(step).lower(params, views, batch).compile().as_text()
    return op_scopes(text, program)
