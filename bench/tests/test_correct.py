"""``correct`` on the timed path: true when sound, false under each
fault a serving cell can have, and the fp8 control beyond the limit.

Each run skips the harness's look for a chip and drives the rest of a
run (``bench.entries.gateway.run``) on the CPU at toy widths, with the
program broken underneath where a test says so."""

import json
import time
import types
from pathlib import Path

import jax
import pytest

from bench import tracing, traffic
from bench.arch import program_arch
from bench.cell import Cell
from bench.entries import gateway
from bench.faults import FAULTS, plant

DATA = Path(__file__).resolve().parent / "data"
CELLS = {"decode": ("tiny-qwen", "tiny-decode"),
         "prompt": ("tiny-olmo", "tiny-prompt")}


def tiny_cell(kind: str) -> Cell:
    cfg, mix = CELLS[kind]
    return Cell(name=f"tiny.{kind}", chips=1,
                config=json.loads((DATA / f"{cfg}.json").read_text()),
                mix=json.loads((DATA / f"{mix}.json").read_text()),
                end_to_end=[{"name": "tok_s", "unit": "tokens/s"},
                            {"name": "tpot_p50_ms", "unit": "ms"},
                            {"name": "tpot_p95_ms", "unit": "ms"},
                            {"name": "setup_s", "unit": "s"}],
                per_layer=[])


def run_cell(kind: str, monkeypatch, seed: int = 2 ** 31 + 11,
             seconds: float = 60.0) -> dict:
    monkeypatch.setattr(gateway, "peaks_for", lambda kind: {})
    now = time.perf_counter()
    return gateway.run(tiny_cell(kind), seed, seconds, False,
                       {"start": now, "devices": now}, jax.devices())


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct(kind, monkeypatch):
    r = run_cell(kind, monkeypatch)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"tok_s", "tpot_p50_ms", "tpot_p95_ms",
                                 "setup_s"}


def test_window_cut_at_the_deadline_counts_exactly(monkeypatch):
    """A window cut inside the queue: the tokens counted are those emitted
    and the prompt tokens ingested by the step of the cut, and the
    requests still running are neither failed nor sampled."""
    monkeypatch.setattr(gateway, "peaks_for", lambda kind: {})
    cell = tiny_cell("prompt")
    cfg, mix = cell.config, cell.mix
    arch = program_arch(cfg)
    params = gateway.make_params(cfg, arch, 3)
    gw = gateway.build_gateway(arch, params, mix, 3)
    win = gateway.Window()
    reqs = gateway.queue_requests(mix, 3, arch.vocab, win)
    win.deadline = 0.0              # the first token ends the window
    with pytest.raises(gateway.DeadlineStop):
        gw.run(reqs)
    step, c = gw.step_count, mix["prefill_chunk"]
    assert all(not r.out_tokens for r in reqs)
    running = [r for r in reqs if r.admitted_step >= 0]
    assert len(running) == mix["slots"]
    # each running slot has taken C prompt tokens a step since admission,
    # and the one that would have emitted has its whole prompt in
    for r in running:
        n = gateway.ingested(r, step, c)
        assert n == min(r.prompt_len, c * (step - r.admitted_step + 1))
    assert max(gateway.ingested(r, step, c) - r.prompt_len
               for r in running) == 0
    assert any(gateway.ingested(r, step, c) == r.prompt_len
               for r in running)
    assert not any(gateway.failed(r) for r in reqs)
    assert gateway.sample(reqs, 3, mix["check"]) == []


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_run_incorrect(kind, fault, monkeypatch):
    plant(fault, monkeypatch.setattr)
    r = run_cell(kind, monkeypatch)
    assert not r["correct"], r["checks"]
    c = r["checks"]["max_gap_sigma"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_fp8_control_exceeds_the_limit(kind, monkeypatch):
    """The reference put in the program's place at fp8, judged by the
    run's own ``judge`` on the same served requests, is not correct,
    while the program is (``bench/control.py`` on the CPU)."""
    from bench import control

    monkeypatch.setattr(gateway, "peaks_for", lambda kind: {})
    r = control.readings(tiny_cell(kind), 2 ** 31 + 11, 60.0)
    assert r["program_correct"] and not r["control_correct"], r
    assert r["program_max_gap"] <= tiny_cell(kind).mix["check"][
        "limit_gap_sigma"] < r["control_max_gap"]
    assert r["tokens"] > 0 and r["failed"] == 0


def test_traced_run_serves_the_whole_window(monkeypatch):
    """With ``--trace 1`` the window runs on after the profiler stops:
    the readers get the traced span for the trace and, for
    ``mfu.serve``, every position served in the window over its time
    less the profiler's stop."""
    seen = {}

    def reader(ctx):
        seen["ctx"] = ctx
        return 1.0

    # the profiler's start and stop on the host clock, as the trace's span
    marks = []
    stamp = lambda *a: marks.append(time.perf_counter_ns())  # noqa: E731
    trace = lambda d: tracing.Trace(  # noqa: E731
        ops={}, modules={},
        spans=[[gateway.SPAN_TRACED, marks[0], marks[1] - marks[0]]])
    monkeypatch.setattr(gateway, "tracing", types.SimpleNamespace(
        start=stamp, stop=stamp, load=trace,
        busy_seconds=lambda *a: 0.0, top_ops=lambda *a: [],
        idle_gaps=lambda *a: []))
    monkeypatch.setattr(gateway, "peaks_for", lambda kind: {})
    monkeypatch.setattr(gateway, "load_metric", lambda name:
                        types.SimpleNamespace(read=reader))
    cell = tiny_cell("decode")
    cell.per_layer = [{"name": "mfu.serve", "unit": "%"}]
    cell.mix["trace"] = {"start_s": 0.05, "length_s": 0.05}
    now = time.perf_counter()
    r = gateway.run(cell, 2 ** 31 + 11, 60.0, True,
                    {"start": now, "devices": now}, jax.devices())
    assert r["correct"], r["checks"]
    ctx = seen["ctx"]
    # the queue drained long after the traced span: every position of
    # every request counts, over more than the span's time
    sizes = traffic.queue_sizes(cell.mix)
    assert sorted(p for _, p in ctx.counts["spans"]) == sorted(
        traffic.tokens_processed(p, n) for p, n in sizes)
    assert ctx.window_s < ctx.counts["served_s"]
    assert r["device"]["window_s"] == ctx.window_s
    # every counter of the gateway across the span, the step program's
    # scope map and the parameters' PTC leaves reach the readers
    assert {"step_count", "busy_steps", "slot_steps", "kv_live_positions",
            "tokens_out"} <= set(ctx.counts)
    assert 0 < ctx.counts["busy_steps"] <= ctx.counts["step_count"]
    assert ctx.counts["kv_live_positions"] > 0
    assert ctx.ptc_leaves == {"wq", "wk", "wv", "wo", "gate", "up", "down"}
    assert any("s0.attn" in op for op in ctx.scopes.values())
