"""The reduction of what the serving engine itself tells the profiler.

``ServingGateway.run`` wraps each busy step in a ``gateway.step`` span
holding one span per phase (``gateway.admit``, ``stage``, ``gather``,
``forward``, ``scatter``, ``fetch``, ``emit``), and the model's named
scopes (``s{i}.attn``, ``s{i}.mlp`` and one per PTC linear, named as
its leaf of the parameters) reach each op's HLO ``op_name``.  These
functions read both from a ``tracing.Trace`` whose ``spans`` hold the
engine's spans beside the harness's, and from a map of the step
program's instructions to their ``op_name`` (``op_scopes``), built from
the engine's own step function by ``step_scopes``.  A reader names its
layer by a pattern
that one scope of an op's name matches (``s{i}.attn``); the PTC
leaves come from the parameters' layout (``ptc_leaves``), so a model
with other linears needs no edit here.  Like ``bench/tracing.py`` they
are arithmetic, tested on made-up traces.
"""

from __future__ import annotations

import re

from . import tracing

__all__ = ["PHASES", "step_self_ms", "phase_ms",
           "idle_placed", "op_scopes", "ptc_leaves", "leaf_pattern",
           "in_layer", "absent_ops", "layer_seconds", "layer_ms",
           "step_scopes"]

STEP = "gateway.step"
PHASES = ("gateway.admit", "gateway.stage", "gateway.gather",
          "gateway.forward", "gateway.scatter", "gateway.fetch",
          "gateway.emit")
FETCH = "gateway.fetch"
PTC_FACTORS = frozenset(("u", "s", "v"))
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


def ptc_leaves(layout) -> frozenset:
    """The names of the PTC linears in a parameter layout: every dict
    key whose value holds the factors ``u``, ``s`` and ``v``."""
    out = set()

    def walk(tree):
        for key, value in tree.items():
            if isinstance(value, dict):
                if PTC_FACTORS <= value.keys():
                    out.add(key)
                else:
                    walk(value)

    walk(layout)
    return frozenset(out)


def leaf_pattern(leaves) -> str | None:
    """A scope pattern matching any of ``leaves``; None for none."""
    if not leaves:
        return None
    return "^(?:" + "|".join(map(re.escape, sorted(leaves))) + ")$"


def in_layer(op_name: str, pattern: str, exclude=frozenset()) -> bool:
    """Whether some scope of ``op_name`` matches ``pattern`` and none is
    one of ``exclude`` (the PTC leaves, for a layer without them)."""
    parts = op_name.split("/")
    rx = re.compile(pattern)
    return (frozenset(exclude).isdisjoint(parts)
            and any(rx.match(p) for p in parts))


def _step_ops(trace: tracing.Trace, lo: float, hi: float):
    """The step program's ops in [lo, hi] on every device plane, as
    (name, start, end) clipped to it; loops and calls are left out, as
    their time is that of the ops they hold."""
    step_op = re.compile(STEP_PROGRAM + "/")
    for evs in trace.ops.values():
        for name, s, e in tracing._clip(evs, lo, hi):
            if step_op.match(name) and not tracing.CONTAINER.search(name):
                yield name, s, e


def absent_ops(trace: tracing.Trace, scopes: dict, lo: float,
               hi: float) -> int:
    """How many of the step program's ops in [lo, hi] the scope map
    does not know: 0 where the map is that of the program that ran."""
    return sum(name not in scopes for name, _, _ in _step_ops(trace, lo, hi))


def layer_seconds(trace: tracing.Trace, scopes: dict, lo: float, hi: float,
                  pattern: str, exclude=frozenset()) -> tuple[float, int]:
    """(device seconds of the step program's ops in [lo, hi] that lie
    in the layer, as ``in_layer`` decides, summed over the device
    planes; ops absent from ``scopes``)."""
    inside = {name for name, op in scopes.items()
              if in_layer(op, pattern, exclude)}
    seconds, absent = 0.0, 0
    for name, s, e in _step_ops(trace, lo, hi):
        if name not in scopes:
            absent += 1
        elif name in inside:
            seconds += (e - s) / 1e9
    return seconds, absent


def layer_ms(ctx, pattern: str | None, exclude_ptc: bool = False):
    """Device ms of a layer per run of the step program in a reader's
    context: ops with a scope matching ``pattern`` and, with
    ``exclude_ptc``, none of the PTC leaves (``ctx.ptc_leaves``).  None
    without a pattern, without a scope map (``ctx.scopes``) in which
    some op lies in the layer, and where the map misses any op of the
    step program in the span: it is then not the map of the program
    that ran, and the layer would read low."""
    scopes = getattr(ctx, "scopes", None)
    exclude = getattr(ctx, "ptc_leaves", frozenset()) if exclude_ptc else ()
    if not pattern or not scopes or not any(
            in_layer(op, pattern, exclude) for op in scopes.values()):
        return None
    _, n = tracing.module_runs(ctx.trace, STEP_PROGRAM, ctx.lo, ctx.hi)
    if not n:
        return None
    seconds, absent = layer_seconds(ctx.trace, scopes, ctx.lo, ctx.hi,
                                    pattern, exclude)
    if absent:
        return None
    return 1e3 * seconds / n


def step_scopes(gw) -> dict:
    """The scope map of the step program a serving gateway runs: its own
    jitted step function, lowered at the shapes of the views it gathers
    (``_gather_views``, traced without running) and of one step's batch
    (``slots`` x the prefill chunk), and compiled.  Where the persistent
    cache holds that program the compile is a load; the instructions
    and their scopes are those of the program the engine ran, and
    ``absent_ops`` shows where they are not.  Call it after the window:
    it traces the model."""
    import jax
    import jax.numpy as jnp

    b, c = gw.gcfg.slots, gw.chunk
    batch = {"token": jax.ShapeDtypeStruct((b, c), jnp.int32),
             "lens": jax.ShapeDtypeStruct((b,), jnp.int32)}
    if c > 1:
        batch["n_valid"] = jax.ShapeDtypeStruct((b,), jnp.int32)
    views = jax.eval_shape(gw._gather_views)
    lowered = gw._step_fn.lower(gw.params, views, batch)
    program = "prefill_step" if c > 1 else "gateway_step"
    return op_scopes(lowered.compile().as_text(), program)
