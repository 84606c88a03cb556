"""The window's own arithmetic: time per output token over runs of
gaps, the engine's pauses, and the cycle collector's log."""

import gc
import types

import pytest

from bench.entries import gateway


def _req(stamps):
    out = gateway.StampedTokens(gateway.Window())
    out.stamps = list(stamps)
    return types.SimpleNamespace(out_tokens=out)


def test_tpot_spans_are_runs_of_gaps_that_do_not_overlap():
    g = gateway.TPOT_GAPS
    # 2g + 3 tokens at 0.1 s a gap: two whole runs, the rest dropped
    a = _req([0.1 * i for i in range(2 * g + 3)])
    # a request with fewer than g + 1 tokens has no run
    b = _req([0.0, 1.0, 2.0])
    # one run with a pause of 1 s inside it
    c = _req([0.1 * i + (1.0 if i >= 5 else 0.0) for i in range(g + 1)])
    got = gateway.tpot_spans([a, b, c])
    assert got == pytest.approx([0.1, 0.1, 0.1 + 1.0 / g])


def test_pauses_name_the_step_and_the_time_lost():
    steps = [(s, 0.1 * s) for s in range(10)]
    # step 10 comes 0.5 s late; steps 11-12 emitted no token (prefill)
    steps += [(10, 1.5), (13, 1.8)]
    got = gateway.pauses(steps)
    assert len(got) == 1
    step, at, lost = got[0]
    assert step == 9 and at == pytest.approx(0.9)
    assert lost == pytest.approx(0.5)


def test_gc_log_counts_collections_by_generation():
    with gateway.GcLog() as log:
        gc.collect(0)
        gc.collect(2)
    assert [g for g, _ in log.runs] == [0, 2]
    assert log.summary().startswith("2 collections (by generation [1, 0, 1])")
