"""The trace reduction: by hand on a made-up trace, and on a small trace
recorded on a TPU v5e (``data/``)."""

import gzip
import json
from pathlib import Path

import pytest

from bench import tracing

DATA = Path(__file__).resolve().parent / "data"

# one device, window [0, 100] ns: ops at [10, 30], [20, 40] (overlap),
# [60, 70] and [95, 120] (cut by the window's end)
MADE_UP = tracing.Trace(
    ops={"/device:TPU:0": [["fusion.1", 10, 20], ["custom-call.7", 20, 20],
                           ["fusion.2", 60, 10], ["copy.3", 95, 25]]},
    modules={"/device:TPU:0": [["jit_gateway_step(1)", 10, 30],
                               ["jit_paged_gather(2)", 60, 10]]},
    spans=[["bench.round", 0, 100], ["bench.inner", 40, 10]])


def test_busy_is_the_union_inside_the_window():
    assert tracing.busy_seconds(MADE_UP, 0, 100) == pytest.approx(
        (30 + 10 + 5) / 1e9)


def test_op_and_module_sums_by_pattern():
    assert tracing.op_seconds(MADE_UP, r"^fusion", 0, 100) == (
        pytest.approx(30e-9), 2)
    assert tracing.module_runs(MADE_UP, r"^jit_gateway_step\b", 0, 100) == (
        pytest.approx(30e-9), 1)


def test_top_ops_leave_out_loops():
    looped = tracing.Trace(
        ops={"/device:TPU:0": MADE_UP.ops["/device:TPU:0"]
             + [["while.3", 0, 90]]},
        modules=MADE_UP.modules, spans=MADE_UP.spans)
    top = tracing.top_ops(looped, 0, 100)
    assert top[0] == ["fusion.1", pytest.approx(20e-9)]
    assert [k for k, _ in top] == ["fusion.1", "custom-call.7", "fusion.2",
                                   "copy.3"]
    # the loop still counts as busy: [0, 90] and [95, 100]
    assert tracing.busy_seconds(looped, 0, 100) == pytest.approx(95e-9)


def test_idle_gaps_named_by_innermost_span():
    gaps = tracing.idle_gaps(MADE_UP, 0, 100)
    # gaps: [0, 10], [40, 60] (mid 50: inner span ends at 50), [70, 95]
    assert gaps[0] == ["bench.round", pytest.approx(25e-9)]
    assert sorted(g[1] for g in gaps) == pytest.approx(
        [10e-9, 20e-9, 25e-9])
    assert ["bench.inner", pytest.approx(20e-9)] in gaps


def test_window_is_the_named_span():
    assert MADE_UP.window("bench.round") == (0, 100)
    with pytest.raises(KeyError):
        MADE_UP.window("bench.none")


@pytest.fixture(scope="module")
def recorded():
    """Four chunked-prefill gateway steps of olmo-1b at 12 slots, C=32,
    traced on a TPU v5e inside one ``bench.round`` span
    (``record_trace.py``)."""
    import jax

    raw = gzip.open(DATA / "prompt-heavy-4steps.xplane.pb.gz").read()
    return tracing.from_profile(
        jax.profiler.ProfileData.from_serialized_xspace(raw))


@pytest.fixture(scope="module")
def recorded_context():
    """What the same run gave a reader's context: the gateway's counters
    across the span, the step program's scope map, the PTC leaves and
    the pool's rows."""
    with gzip.open(DATA / "prompt-heavy-4steps.context.json.gz", "rt") as f:
        return json.load(f)


def test_recorded_trace_reads_as_by_hand(recorded):
    # the sums below were read from the same trace by a separate loop
    # over ProfileData's raw events
    lo, hi = recorded.window("bench.round")
    assert (hi - lo) / 1e9 == pytest.approx(0.282332475, rel=1e-6)
    assert list(recorded.ops) == ["/device:TPU:0"]
    assert tracing.busy_seconds(recorded, lo, hi) == pytest.approx(
        0.268629449, rel=1e-4)
    assert tracing.module_runs(recorded, r"^jit_prefill_step\b", lo, hi) == (
        pytest.approx(0.218334174, rel=1e-6), 4)
    assert tracing.op_seconds(recorded, r"^jit_paged_gather/paged_gather\b",
                              lo, hi) == (pytest.approx(0.035036021,
                                                        rel=1e-6), 8)
    assert tracing.op_seconds(recorded, r"/paged_scatter\b", lo, hi) == (
        pytest.approx(0.014591221, rel=1e-6), 8)
    assert tracing.op_seconds(recorded, r"^jit_prefill_step/prefill_attention",
                              lo, hi) == (pytest.approx(0.059613933,
                                                        rel=1e-6), 64)
    # every op lies inside a program
    assert all("/" in name for name, _, _ in recorded.ops["/device:TPU:0"])


def test_recorded_trace_breakdown(recorded):
    lo, hi = recorded.window("bench.round")
    top = dict(tracing.top_ops(recorded, lo, hi))
    assert not any("/while" in k for k in top)
    assert top["jit_prefill_step/prefill_attention.3"] == pytest.approx(
        0.059613933, rel=1e-6)
    # the two copies named copy.1 belong to different programs
    every = dict(tracing.top_ops(recorded, lo, hi, n=None))
    assert {"jit_reshape/copy.1", "jit_paged_scatter/copy.1"} <= set(every)
    assert set(top) <= set(every)
    gaps = tracing.idle_gaps(recorded, lo, hi)
    assert gaps and all(name == "bench.round" for name, _ in gaps)
    busy = tracing.busy_seconds(recorded, lo, hi)
    assert sum(g for _, g in gaps) <= (hi - lo) / 1e9 - busy + 1e-9


def test_readers_on_the_recorded_trace(recorded, recorded_context):
    """Every per-layer reader of the azure-conv cell gives a number on
    a real trace of that cell's shapes, with the counters, scope map and
    PTC leaves of the same run; no share passes 100%, and attention and
    the PTC linears take part of the step program's time."""
    import jax.numpy as jnp

    from bench.cell import find_cell, load_metric
    from bench.entries.gateway import MetricContext
    from bench.peaks import peaks_for

    cell = find_cell("olmo-1b.azure-conv")
    lo, hi = recorded.window("bench.round")
    rec = recorded_context
    # 12 requests of 40 prompt tokens and 3 new, from a pool of 16
    # layers of (16 heads x 128) bf16 rows for k and v
    assert (rec["requests"], rec["prompt_len"], rec["new_tokens"]) == (
        12, 40, 3)
    pool = {name: {kk: (tuple(row), jnp.dtype(dt))
                   for kk, (row, dt) in t.items()}
            for name, t in rec["kv_pool"].items()}
    assert pool == {"pos0": {"k": ((16, 128), jnp.bfloat16),
                             "v": ((16, 128), jnp.bfloat16)}}
    # each slot attends over 32, 40 (32 + 8), 41 and 42 positions
    assert rec["counts"]["kv_live_positions"] == 12 * (32 + 40 + 41 + 42)
    ctx = MetricContext(cfg=cell.config, mix=cell.mix,
                        peaks=peaks_for("TPU v5 lite"), trace=recorded,
                        lo=lo, hi=hi, window_s=(hi - lo) / 1e9,
                        counts={**rec["counts"], "slots": 12,
                                "spans": [(0, 42)] * 12,
                                "served_s": (hi - lo) / 1e9,
                                "kv_pool": pool,
                                "kv_layers": rec["kv_layers"]},
                        scopes=rec["scopes"],
                        ptc_leaves=frozenset(rec["ptc_leaves"]))
    got = {m["name"]: load_metric(m["name"]).read(ctx)
           for m in cell.per_layer}
    assert {"kv_view_live.serve", "attn_ms.serve", "ptc_ms.serve"} <= set(got)
    assert all(v is not None for v in got.values()), got
    assert got["occupancy.serve"] == pytest.approx(100.0)
    assert got["step_ms.serve"] == pytest.approx(218.334174 / 4, rel=1e-6)
    assert got["kv_view_live.serve"] == pytest.approx(
        100.0 * 12 * 155 / (4 * 12 * 2048))
    # 64 calls of (12 x 32) queries of 16 heads of 128 against (12, 2048)
    # views of 16 x 128 for k and v, bf16: 4·b·h·c·s·dh FLOPs; q, out and
    # both views read or written
    f = 4 * 12 * 16 * 32 * 2048 * 128
    b = 2 * (2 * 12 * 32 * 16 * 128 + 2 * 12 * 2048 * 16 * 128)
    t = max(f / 197e12, b / 819e9)
    assert got["prefill_attn_roofline"] == pytest.approx(
        100.0 * 64 * t / 0.059613933, rel=1e-5)
    # the kernel lies under s{i}.attn; attention and PTC do not overlap
    assert got["attn_ms.serve"] >= 1e3 * 0.059613933 / 4
    assert got["ptc_ms.serve"] > 0
    assert got["attn_ms.serve"] + got["ptc_ms.serve"] < got["step_ms.serve"]
    for name, v in got.items():
        if not name.endswith("_ms.serve"):
            assert 0.0 < v <= 100.0, (name, v)
