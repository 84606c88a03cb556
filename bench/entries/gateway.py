"""Serving cells: the continuous-batching gateway on an offline queue.

``repro.serving.engine.ServingGateway`` is built once per process and
``run`` is called once, on the mix's queue: a fixed list of request
sizes, all due at the start, long enough that the slots stay full for
the whole window (``bench/traffic.py``); the seed draws the token ids.
``run`` serves a fixed list and takes no request while it runs, so
these cells are offline batches, not an open loop.

Per-token wall time: each request's ``out_tokens`` is a list that stamps
``time.perf_counter()`` on ``append``.  The engine appends a token right
after its blocking device-to-host argmax, so the stamp is when the host
holds the token.

Window: from the end of set-up, cut exactly at ``--seconds``.  The first
token appended at or after the deadline is not recorded: its ``append``
raises, which ends ``run`` in the middle of that step, and the gateway
is thrown away.  The window holds what the engine had done by then,
counted exactly to the step: every token emitted, and every prompt token
ingested, which for a request still in prefill is C per step since its
admission (``ingested``).  A request that finished inside the window
with another token count than it asked for, or another stamp count than
token count, has failed; one still running at the cut has not.  The
collector's heap is frozen before the window, and every collection and
every pause of the engine inside it is printed.

The traced run (``--trace 1``) serves the same window.  It starts the
profiler at the first token of the mix's ``trace.start_s`` and stops it
at the first token ``trace.length_s`` later, so the trace holds a few
seconds of steady serving and stays small; the per-layer metrics read
that span, but for ``mfu.serve``, which counts the positions served in
the whole window over its time less the seconds the profiler took to
stop inside it (``served_s``), as ``tok_s`` counts the untraced
window.  The readers of the paged KV kernels take the bytes of a
cached row from the pools the gateway built (``kv_pool``), not from
the configuration.  Every public integer attribute of the gateway
(its counters, ``engine_counters``) reaches the readers as its
difference across the traced span, under its own name, and the scope
map of the step program (``engine_trace.step_scopes``, from the
engine's own step function after the window) with the PTC leaves of the
parameters, so a counter or a scope
that a later engine or model adds needs only a reader of its own.

``correct``: once the window has closed and the gateway is freed, a
sample of the requests that finished in it, drawn from the seed, the
longest among them, is run through the configuration's plain reference
(float32, HIGHEST) over its prompt and served tokens.  The number
compared is the widest gap by which a served token's reference logit
lies below the reference's best at that position, in units of the
spread of that position's reference logits (``judge``).
"""

from __future__ import annotations

import dataclasses
import gc
import math
import numbers
import shutil
import sys
import tempfile
import time

import numpy as np

from .. import engine_trace, tracing, traffic
from ..arch import kv_pool, program_arch
from ..cell import Cell, load_metric, load_reference
from ..compiles import CompileClock
from ..peaks import peaks_for
from ..weights import make_weights, seed_key

__all__ = ["run", "make_params", "build_gateway", "queue_requests", "serve",
           "failed", "ingested", "positions", "tpot_spans", "sample",
           "reference_gaps", "judge", "engine_counters", "counts_across",
           "Window", "StampedTokens", "DeadlineStop", "MetricContext"]

SPAN_WINDOW = "bench.window"
SPAN_TRACED = "bench.traced"
# time per output token is read over runs of 15 consecutive gaps of one
# request: at a step of 17 ms or more that spans the 250 ms a host-clock
# time needs
TPOT_GAPS = 15


class DeadlineStop(Exception):
    """Raised from a token append at the window's end."""


class Window:
    """What every token list of a run shares: the deadline, the moment
    the cut came, a hook to run once ``hook_after`` seconds into the
    window, and the host-clock time of each engine step's first token
    (``steps``, when ``step_of`` reads the engine's step count)."""

    def __init__(self):
        self.deadline = math.inf
        self.cut_at: float | None = None
        self.hook_after = math.inf
        self.hook_at = math.inf
        self.hook = None
        self.step_of = None
        self.steps: list[tuple[int, float]] = []


class StampedTokens(list):
    """A token list that records the host clock at every append, and
    ends the window at its deadline."""

    def __init__(self, window: Window):
        super().__init__()
        self.window = window
        self.stamps: list[float] = []

    def append(self, tok) -> None:
        now = time.perf_counter()
        w = self.window
        if now >= w.deadline:
            w.cut_at = now
            raise DeadlineStop
        if now >= w.hook_at:
            w.hook_at = math.inf
            w.hook()
        if w.step_of is not None:
            step = w.step_of()
            if not w.steps or w.steps[-1][0] != step:
                w.steps.append((step, now))
        self.stamps.append(now)
        super().append(tok)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer reader gets (``bench/metrics/<name>.py``)."""

    cfg: dict                   # the configuration file
    mix: dict                   # the traffic mix file
    peaks: dict                 # the chip's published peaks
    trace: tracing.Trace
    lo: float                   # traced span on the trace clock, ns
    hi: float
    window_s: float             # its length, seconds
    # the engine's counts (``run``): each public integer attribute of the
    # gateway as its difference across the traced span, so a setting
    # (``chunk``, ``stride``, ``n_periods``) reads 0; then ``slots``,
    # ``spans``, ``served_s``, ``kv_pool`` and ``kv_layers``
    counts: dict
    # the step program's {op: HLO op_name} (engine_trace.step_scopes)
    scopes: dict = dataclasses.field(default_factory=dict)
    ptc_leaves: frozenset = frozenset()     # engine_trace.ptc_leaves


def engine_counters(gw) -> dict:
    """Every public integer attribute of the gateway: its counters
    (``step_count``, ``busy_steps``, ``slot_steps``,
    ``kv_live_positions``, ``tokens_out``, and any a later engine adds)
    and its integer settings (``chunk``, ``stride``, ``n_periods``),
    which read 0 as a difference."""
    return {k: v for k, v in vars(gw).items()
            if not k.startswith("_") and isinstance(v, numbers.Integral)
            and not isinstance(v, bool)}


def counts_across(before: dict, after: dict, **fixed) -> dict:
    """Each counter's difference from ``before`` to ``after`` (so a
    setting reads 0), under its own name, then ``fixed``; a name in
    both is an error, as one would hide the other."""
    both = sorted(set(after) & set(fixed))
    if both:
        raise ValueError(f"the gateway's counters {both} have the names "
                         f"of the harness's counts")
    return {**{k: v - before.get(k, 0) for k, v in after.items()}, **fixed}


def build_gateway(arch, params, mix: dict, seed: int):
    """The gateway at the mix's geometry, warmed up: one short request
    compiles (or loads) every program of a step at the cell's shapes,
    which never depend on the queue."""
    from repro.serving.engine import GatewayConfig, ServingGateway
    from repro.serving.kv_pages import PageConfig
    from repro.serving.scheduler import Request

    gcfg = GatewayConfig(
        slots=mix["slots"], max_steps=2 ** 62,
        pages=PageConfig(page_size=mix["page_size"], n_pages=mix["n_pages"],
                         max_pages_per_slot=mix["max_pages_per_slot"]),
        prefill_chunk=mix["prefill_chunk"])
    gw = ServingGateway(arch, params, gcfg)
    w = mix["warmup"]
    rng = np.random.default_rng([seed, 1])
    gw.run([Request(rid=-1, max_new=w["output_len"],
                    prompt=rng.integers(0, arch.vocab, w["prompt_len"])
                    .astype(np.int32))])
    return gw


def make_params(cfg: dict, arch, seed: int):
    """The benchmark's weights for ``arch`` from ``seed``, on the device."""
    import jax

    from repro.models.lm import init_model

    key = seed_key(seed)
    layout = jax.eval_shape(lambda k: init_model(k, arch), key)
    params = make_weights(key, layout, arch.d_model,
                          cfg["assumed"]["embed_scale"])
    return jax.block_until_ready(params)


def queue_requests(mix: dict, seed: int, vocab: int, window: Window):
    """The mix's queue, its token lists tied to ``window``."""
    from repro.serving.scheduler import Request

    sizes = traffic.queue_sizes(mix)
    prompts = traffic.queue_tokens(mix, seed, vocab)
    reqs = []
    for i, ((_, n_out), prompt) in enumerate(zip(sizes, prompts)):
        r = Request(rid=i, prompt=prompt, max_new=n_out)
        r.out_tokens = StampedTokens(window)
        reqs.append(r)
    return reqs


def serve(gw, reqs: list, win: Window, seconds: float
          ) -> tuple[float, float]:
    """Serve the queue until ``seconds`` have passed (or it drains);
    the window's start and end on the host clock."""
    import jax

    win.step_of = lambda: gw.step_count
    t0 = time.perf_counter()
    win.deadline = t0 + seconds
    win.hook_at = t0 + win.hook_after
    try:
        with jax.profiler.TraceAnnotation(SPAN_WINDOW):
            gw.run(reqs)
    except DeadlineStop:
        pass
    return t0, win.cut_at or time.perf_counter()


def failed(req) -> bool:
    """A request that finished with a wrong token or stamp count."""
    out = req.out_tokens
    return req.done and (req.finish_reason != "max_new"
                         or len(out) != req.max_new
                         or len(out.stamps) != len(out))


def ingested(req, step: int, chunk: int) -> int:
    """Prompt tokens the request had run through the model by the end of
    engine step ``step``: all of them once it emitted a token, else
    ``chunk`` per step since its admission, as the engine feeds a
    prefilling slot."""
    if req.out_tokens:
        return req.prompt_len
    if req.admitted_step < 0:
        return 0
    return min(req.prompt_len, chunk * (step - req.admitted_step + 1))


def positions(reqs: list, step: int, chunk: int) -> list[int]:
    """Positions each request had run through the model by ``step``:
    its ingested prompt and every emitted token but the last."""
    return [traffic.tokens_processed(ingested(r, step, chunk),
                                     len(r.out_tokens)) for r in reqs]


def tpot_spans(reqs) -> list[float]:
    """Time per output token, seconds, over each run of ``TPOT_GAPS``
    consecutive gaps of one request in the window, runs not overlapping:
    every request's tokens, from its first."""
    out = []
    for r in reqs:
        st = r.out_tokens.stamps
        out += [(st[i] - st[i - TPOT_GAPS]) / TPOT_GAPS
                for i in range(TPOT_GAPS, len(st), TPOT_GAPS)]
    return out


def pauses(steps: list[tuple[int, float]]) -> list[tuple[int, float, float]]:
    """[(step, host time, seconds lost)]: each stretch between two engine
    steps that emitted tokens which took two median steps longer than
    its steps' share, worst first."""
    if len(steps) < 3:
        return []
    s, t = np.asarray(steps, np.float64).T
    per = np.median(np.diff(t) / np.diff(s))
    lost = np.diff(t) - np.diff(s) * per
    at = np.flatnonzero(lost > 2 * per)
    return sorted(((int(s[i]), float(t[i]), float(lost[i])) for i in at),
                  key=lambda p: -p[2])


class GcLog:
    """Every collection of Python's cycle collector while it is open:
    its generation and seconds."""

    def __init__(self):
        self.runs: list[tuple[int, float]] = []
        self._t = 0.0

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.runs.append((info["generation"],
                              time.perf_counter() - self._t))

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)

    def summary(self) -> str:
        per = [sum(1 for g, _ in self.runs if g == k) for k in range(3)]
        longest = max((d for _, d in self.runs), default=0.0)
        return (f"{len(self.runs)} collections (by generation {per}), "
                f"{sum(d for _, d in self.runs) * 1e3:.1f} ms in all, "
                f"longest {longest * 1e3:.1f} ms")


def sample(reqs, seed: int, check: dict) -> list:
    """The longest request that finished sound, then others in a seeded
    order, until ``min_tokens`` served tokens, in whole reference
    batches."""
    done = [r for r in reqs if r.done and not failed(r)]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + r.max_new, r.rid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 7]).permutation(len(rest))
    picked, n = [longest], longest.max_new
    for i in order:
        if n >= check["min_tokens"] and len(picked) % check["ref_batch"] == 0:
            break
        picked.append(rest[i])
        n += rest[i].max_new
    return picked


def reference_gaps(params, cfg: dict, mix: dict, reqs: list,
                   control: bool = False) -> np.ndarray:
    """Per served token gap (reference best minus served, over the
    position's logit spread) for ``reqs``, concatenated; with
    ``control`` the token scored is the fp8 reference's first choice."""
    import jax.numpy as jnp

    ref = load_reference(cfg["reference"])
    s_max = mix["max_pages_per_slot"] * mix["page_size"]
    o_max = mix["output_len"]["max"]
    b = mix["check"]["ref_batch"]
    gaps = []
    for at in range(0, len(reqs), b):
        chunk = reqs[at:at + b]
        toks = np.zeros((b, s_max), np.int32)
        pos = np.zeros((b, o_max), np.int32)
        served = np.zeros((b, o_max), np.int32)
        valid = np.zeros((b, o_max), bool)
        for i, r in enumerate(chunk):
            out = np.asarray(list(r.out_tokens), np.int32)
            p, n = r.prompt_len, len(out)
            toks[i, :p] = r.prompt
            toks[i, p:p + n - 1] = out[:-1]
            pos[i, :n] = p - 1 + np.arange(n)
            served[i, :n] = out
            valid[i, :n] = True
        g = np.asarray(ref.token_gaps(params, cfg, jnp.asarray(toks),
                                      jnp.asarray(pos), jnp.asarray(served),
                                      control=control))
        gaps.append(g[valid])
    return np.concatenate(gaps) if gaps else np.zeros((0,))


def judge(gaps: np.ndarray, n_failed: int, limit: float
          ) -> tuple[bool, dict]:
    """``correct`` and each number it compares beside its limit: the
    widest gap of the sampled served tokens (none sampled reads as
    infinite), and the requests that failed."""
    max_gap = float(gaps.max()) if gaps.size else math.inf
    checks = {"max_gap_sigma": {"value": max_gap, "limit": limit},
              "failed_requests": {"value": n_failed, "limit": 0}}
    return bool(max_gap <= limit and not n_failed), checks


def run(cell: Cell, seed: int, seconds: float, trace: bool, marks: dict,
        devices: list) -> dict:
    """One run of a serving cell; ``marks`` holds the host-clock times of
    process start (``start``) and of the backend's devices (``devices``)."""
    import jax

    import repro.serving.engine  # noqa: F401  (counted as imports)

    clock = CompileClock()
    t = time.perf_counter()
    # process start to the TPU client, then the program's own imports
    setup = {"start_s": marks["devices"] - marks["start"],
             "imports_s": t - marks["devices"]}
    cfg, mix = cell.config, cell.mix
    chunk = mix["prefill_chunk"]
    dev = devices[0]
    peaks = peaks_for(dev.device_kind)

    arch = program_arch(cfg)
    params = make_params(cfg, arch, seed)
    win = Window()
    served = queue_requests(mix, seed, arch.vocab, win)
    setup["init_s"] = time.perf_counter() - t

    t, c0 = time.perf_counter(), clock.seconds
    gw = build_gateway(arch, params, mix, seed)
    setup["compile_s"] = clock.seconds - c0
    setup["warmup_s"] = time.perf_counter() - t - setup["compile_s"]
    # what set-up built stays: no collection inside the window walks it
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - marks["start"]

    # -- the window ------------------------------------------------------
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
    span: dict = {}

    def start_trace():
        # snapshot the counters and the slots' cached lengths, trace, and
        # stop trace.length_s from here
        span["counters"] = [engine_counters(gw)]
        span["lens"] = np.array(gw.pool.lens)
        tracing.start(trace_dir)
        span["annotation"] = jax.profiler.TraceAnnotation(SPAN_TRACED)
        span["annotation"].__enter__()
        win.hook_at = time.perf_counter() + mix["trace"]["length_s"]
        win.hook = stop_trace

    def stop_trace():
        t = time.perf_counter()
        span["counters"].append(engine_counters(gw))
        span["annotation"].__exit__(None, None, None)
        tracing.stop()
        span["stop_s"] = time.perf_counter() - t

    if trace:
        win.hook_after = max(0.0, min(mix["trace"]["start_s"],
                                      seconds - mix["trace"]["length_s"]))
        win.hook = start_trace
    n_compiles = clock.count
    with GcLog() as gc_log:
        t0, t1 = serve(gw, served, win, seconds)
    drained = win.cut_at is None
    if trace:
        if "annotation" not in span:
            raise RuntimeError("the window closed before the traced span "
                               "began: no token came after trace.start_s")
        if "stop_s" not in span:        # the window closed first
            stop_trace()
            span["stop_s"] = 0.0
    cut_step = gw.step_count
    window_compiles = clock.count - n_compiles
    pool, n_periods = kv_pool(gw), gw.n_periods
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if trace:
        t = time.perf_counter()
        scopes = engine_trace.step_scopes(gw)
        scopes_s = time.perf_counter() - t
    del gw
    gc.unfreeze()
    gc.collect()

    bad = [r for r in served if failed(r)]
    started = [r for r in served if r.admitted_step >= 0]
    spans = tpot_spans(served)
    metrics = {}
    if not trace:
        tokens = sum(ingested(r, cut_step, chunk) + len(r.out_tokens)
                     for r in served)
        tpot = (np.percentile(spans, [50, 95]) * 1e3 if spans
                else [math.nan] * 2)
        values = {"tok_s": tokens / (t1 - t0),
                  "tpot_p50_ms": float(tpot[0]),
                  "tpot_p95_ms": float(tpot[1]), "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"metrics": metrics, "device": device}
    if trace:
        tr = tracing.load(trace_dir)
        lo, hi = tr.window(SPAN_TRACED)
        # the counters over the traced span; positions served in the
        # window, and its time less the profiler's stop; the pool's rows
        # and the layers a gather or scatter call covers
        counts = counts_across(
            *span["counters"], slots=mix["slots"],
            spans=[(0, p) for p in positions(served, cut_step, chunk)],
            served_s=t1 - t0 - span["stop_s"], kv_pool=pool,
            kv_layers=n_periods)
        lens = span["lens"][span["lens"] > 0]
        print(f"traced span from engine step "
              f"{span['counters'][0]['step_count']}, {lens.size} slots "
              f"holding {lens.mean() if lens.size else 0:.1f} cached "
              f"positions on average; scope map of the step program: "
              f"{len(scopes)} instructions, {scopes_s:.3f} s, step-program "
              f"ops in the span absent from it: "
              f"{engine_trace.absent_ops(tr, scopes, lo, hi)}",
              file=sys.stderr)
        mctx = MetricContext(cfg=cfg, mix=mix, peaks=peaks, trace=tr, lo=lo,
                             hi=hi, window_s=(hi - lo) / 1e9, counts=counts,
                             scopes=scopes,
                             ptc_leaves=engine_trace.ptc_leaves(params))
        for m in cell.per_layer:
            v = load_metric(m["name"]).read(mctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = tracing.busy_seconds(tr, lo, hi)
        device["window_s"] = mctx.window_s
        result["breakdown"] = {"device_ops": tracing.top_ops(tr, lo, hi),
                               "idle_gaps": tracing.idle_gaps(tr, lo, hi)}
    shutil.rmtree(trace_dir)

    # -- correct ---------------------------------------------------------
    t = time.perf_counter()
    picked = sample(served, seed, mix["check"])
    gaps = reference_gaps(params, cfg, mix, picked)
    correct, checks = judge(gaps, len(bad), mix["check"]["limit_gap_sigma"])
    log = sys.stderr
    print(f"setup {setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f}" for k, v in setup.items()), file=log)
    print(f"window {t1 - t0:.3f} s, {cut_step} engine steps in all, "
          f"{len(started)} requests started, "
          f"{sum(r.done for r in served)} finished, {len(spans)} runs of "
          f"{TPOT_GAPS} gaps timed, compiles inside the window "
          f"{window_compiles}", file=log)
    if drained:
        print(f"the queue of {len(served)} drained before the deadline: "
              f"the window ends with its last token", file=log)
    lost = pauses(win.steps)
    print(f"pauses in the window (over two median steps): {len(lost)}, "
          f"{sum(p[2] for p in lost):.3f} s lost; worst (step, s into the "
          f"window, ms lost): " + ", ".join(
              f"({s}, {at - t0:.3f}, {d * 1e3:.1f})" for s, at, d in lost[:5]),
          file=log)
    print(f"cycle collector in the window: {gc_log.summary()}", file=log)
    if trace:
        print(f"profiler stop inside the window: {span['stop_s']:.3f} s",
              file=log)
    print(f"reference over {len(picked)} requests, {gaps.size} served "
          f"tokens, {time.perf_counter() - t:.3f} s", file=log)
    result.update(correct=correct, attempted=len(started), failed=len(bad),
                  checks=checks)
    return result
