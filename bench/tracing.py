"""Profiler capture and the reduction from a trace to numbers.

Two stages.  ``load`` reads the ``.xplane.pb`` that ``jax.profiler``
wrote (``jax.profiler.ProfileData``, nothing but JAX) into a plain
``Trace``: per device plane, the events of its op and module lines, and
the harness's own host spans (``bench.*`` TraceAnnotations).  The rest
is arithmetic on that ``Trace`` and needs no JAX, so it is tested on a
small recorded trace (``bench/tests/data``).

Names as a TPU v5e trace shows them (read by hand from traces of both
serving cells): device planes ``/device:TPU:<n>``; the line ``XLA Ops``
holds one event per HLO op, named by the whole instruction text, of
which ``load`` keeps the instruction name (``fusion.274``, ``copy.4``,
``while.16``; a ``while`` holds the ops of its loop body, which also
appear) behind its program's (``jit_paged_scatter/copy.4``).  A Pallas
kernel is the custom call named after the jitted function that calls
it: ``paged_gather.1``, ``paged_scatter.1``, ``prefill_attention.3``.
The line ``XLA Modules`` holds one event per run of a jitted program,
``jit_<function>(<fingerprint>)``:
``jit_gateway_step``, ``jit_prefill_step``, ``jit_paged_gather``,
``jit_paged_scatter``, ``jit_reshape``, ``jit__argmax``.  A third line,
``Async XLA Ops``, holds copies that overlap the ops and is not read.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

__all__ = ["Trace", "start", "stop", "load", "from_profile",
           "busy_seconds", "op_seconds", "module_runs", "idle_gaps",
           "top_ops"]

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS, MODULES = "XLA Ops", "XLA Modules"
SPAN_PREFIX = "bench."
CONTAINER = re.compile(r"(^|/)(while|conditional|call)\b")


@dataclasses.dataclass
class Trace:
    """Events as [name, start_ns, duration_ns], on one clock."""

    ops: dict[str, list]        # device plane -> op events
    modules: dict[str, list]    # device plane -> module events
    spans: list                 # the harness's host spans

    def window(self, span: str) -> tuple[float, float]:
        """[start, end] of the first host span named ``span``."""
        for name, start, dur in self.spans:
            if name == span:
                return start, start + dur
        raise KeyError(f"no host span {span!r} in the trace")


def start(log_dir: str) -> None:
    """Start tracing device activity and host spans, with no Python
    tracer (it would slow the host loop it measures)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def load(log_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return from_profile(jax.profiler.ProfileData.from_file(files[-1]))


def from_profile(data) -> Trace:
    """The ``Trace`` of a ``jax.profiler.ProfileData``.  Each op is named
    ``<program>/<instruction>``, the program being the module event that
    holds it (programs run one at a time on a device)."""
    ops, modules, spans = {}, {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            mods = sorted([e.name, e.start_ns, e.duration_ns]
                          for e in lines[MODULES].events) \
                if MODULES in lines else []
            mods.sort(key=lambda m: m[1])
            modules[plane.name] = mods
            starts = [m[1] for m in mods]
            ops[plane.name] = [
                [_program(mods, starts, e.start_ns) + _short(e.name),
                 e.start_ns, e.duration_ns]
                for e in (lines[OPS].events if OPS in lines else ())]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    if not any(ops.values()):
        raise ValueError("no TPU op events in the trace")
    return Trace(ops=ops, modules=modules, spans=spans)


def _short(name: str) -> str:
    """An op event is named by its whole HLO instruction ("%copy.4 =
    bf16[...] copy(...)"); keep the instruction's name ("copy.4")."""
    return name.split(" = ", 1)[0].lstrip("%")


def _program(mods: list, starts: list, t: float) -> str:
    """``jit_<function>/`` for the module event running at ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= mods[i][1] + mods[i][2]:
        return mods[i][0].split("(", 1)[0] + "/"
    return ""


def _clip(events, lo, hi):
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            yield name, s, e


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(trace: Trace, lo: float, hi: float) -> float:
    """Seconds in [lo, hi] in which some op ran, averaged over the
    device planes."""
    per = [sum(e - s for s, e in _union((s, e) for _, s, e in
                                        _clip(evs, lo, hi)))
           for evs in trace.ops.values()]
    return sum(per) / len(per) / 1e9


def op_seconds(trace: Trace, pattern: str, lo: float, hi: float
               ) -> tuple[float, int]:
    """(seconds, count) of ops whose name matches ``pattern``, summed
    over the device planes, clipped to the window."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for evs in trace.ops.values():
        for name, s, e in _clip(evs, lo, hi):
            if rx.search(name):
                total += e - s
                n += 1
    return total / 1e9, n


def module_runs(trace: Trace, pattern: str, lo: float, hi: float
                ) -> tuple[float, int]:
    """(seconds, count) of runs of jitted programs whose name matches."""
    rx = re.compile(pattern)
    total, n = 0.0, 0
    for evs in trace.modules.values():
        for name, s, e in _clip(evs, lo, hi):
            if rx.search(name):
                total += e - s
                n += 1
    return total / 1e9, n


def top_ops(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[op, seconds], ...]: the ops that took most device time.  Loops
    and calls (``while``, ``conditional``, ``call``) are left out, as
    their time is that of the ops they hold."""
    sums: dict[str, float] = {}
    for evs in trace.ops.values():
        for name, s, e in _clip(evs, lo, hi):
            if not CONTAINER.search(name):
                sums[name] = sums.get(name, 0.0) + (e - s) / 1e9
    return sorted(([k, v] for k, v in sums.items()),
                  key=lambda kv: -kv[1])[:n]


def idle_gaps(trace: Trace, lo: float, hi: float, n: int = 10) -> list:
    """[[host span, seconds], ...]: the longest gaps in which no op ran
    on the first device, each named by the innermost harness span that
    covers its midpoint."""
    evs = next(iter(trace.ops.values()))
    busy = _union((s, e) for _, s, e in _clip(evs, lo, hi))
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) / 2
        covering = [(dur, name) for name, st, dur in trace.spans
                    if st <= mid <= st + dur]
        out.append([min(covering)[1] if covering else "outside any span",
                    (e - s) / 1e9])
    return out
