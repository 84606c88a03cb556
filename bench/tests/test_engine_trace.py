"""The reduction of the engine's own spans and the step program's scopes
(``bench/engine_trace.py``) and the readers built on it: by hand on a
made-up trace and a made-up HLO text, and the scope map of a tiny
gateway's step program on the CPU.  The channels a later model or engine
uses without an edit: a counter the gateway adds, a PTC leaf of another
name, a layer of another scope."""

import dataclasses
import types

import numpy as np
import pytest

from bench import engine_trace, tracing
from bench.cell import load_metric

# phase lengths of one made-up engine step, ns: admit, stage, gather,
# forward, scatter, fetch, emit (300 in all; the fetch waits 190)
LENGTHS = (10, 20, 20, 20, 20, 190, 20)


def _step_spans(t):
    spans = [["gateway.step", t, sum(LENGTHS)]]
    for phase, d in zip(engine_trace.PHASES, LENGTHS):
        spans.append([phase, t, d])
        t += d
    return spans


def _step_ops(t):
    # gather at [t+40, t+60]; the step program [t+60, t+270], idle for
    # [t+200, t+210] inside the fetch
    return [["jit_paged_gather/paged_gather.1", t + 40, 20],
            ["jit_gateway_step/while.4", t + 60, 100],
            ["jit_gateway_step/fusion.1", t + 60, 100],
            ["jit_gateway_step/fusion.2", t + 160, 40],
            ["jit_gateway_step/fusion.2", t + 210, 40],
            ["jit_gateway_step/copy.3", t + 250, 15],
            ["jit_gateway_step/fusion.9", t + 265, 5]]


# one device, window [0, 1000] ns, engine steps at 100 and 400
MADE_UP = tracing.Trace(
    ops={"/device:TPU:0": _step_ops(100) + _step_ops(400)},
    modules={"/device:TPU:0": [["jit_paged_gather(1)", 140, 20],
                               ["jit_gateway_step(2)", 160, 210],
                               ["jit_paged_gather(1)", 440, 20],
                               ["jit_gateway_step(2)", 460, 210]]},
    spans=[["bench.traced", 0, 1000]] + _step_spans(100) + _step_spans(400))
SCOPES = {
    "jit_gateway_step/fusion.1":
        "jit(gateway_step)/while/body/closed_call/s0.attn/dot_general",
    "jit_gateway_step/fusion.2":
        "jit(gateway_step)/while/body/closed_call/s0.attn/wq/dot_general",
    "jit_gateway_step/copy.3": "",
    "jit_gateway_step/while.4": "jit(gateway_step)/while",
}
# the map of the program that ran holds fusion.9 too
FULL = dict(SCOPES, **{"jit_gateway_step/fusion.9": "jit(gateway_step)/add"})
LEAVES = frozenset(("wq", "wk", "wv", "wo", "gate", "up", "down"))
ATTN = r"^s\d+\.attn$"


def test_step_self_time_leaves_out_the_fetch():
    assert engine_trace.step_self_ms(MADE_UP, 0, 1000) == pytest.approx(
        [110e-6, 110e-6])
    # a step that the window cuts is not counted
    assert engine_trace.step_self_ms(MADE_UP, 0, 600) == pytest.approx(
        [110e-6])
    assert engine_trace.phase_ms(MADE_UP, 0, 1000)["gateway.fetch"] == (
        pytest.approx(190e-6))
    bare = tracing.Trace(ops=MADE_UP.ops, modules=MADE_UP.modules,
                         spans=[["bench.traced", 0, 1000]])
    assert engine_trace.step_self_ms(bare, 0, 1000) == []
    assert engine_trace.phase_ms(bare, 0, 1000) == {}


def test_idle_gaps_named_by_engine_phase():
    gaps = tracing.idle_gaps(MADE_UP, 0, 1000)
    # [670, 1000] and [0, 140] outside any step; [370, 440] (mid 405)
    # in the second step's admission; [300, 310] and [600, 610] in the
    # fetches
    assert gaps == [["bench.traced", pytest.approx(330e-9)],
                    ["bench.traced", pytest.approx(140e-9)],
                    ["gateway.admit", pytest.approx(70e-9)],
                    ["gateway.fetch", pytest.approx(10e-9)],
                    ["gateway.fetch", pytest.approx(10e-9)]]
    assert engine_trace.idle_placed(MADE_UP, 0, 1000) == pytest.approx(
        100 * 90 / 560)
    # inside the steps alone every gap lies in a phase
    assert engine_trace.idle_placed(MADE_UP, 100, 700) == pytest.approx(
        100 * 90 / 90)


def test_layer_seconds_by_scope():
    attn = engine_trace.layer_seconds(MADE_UP, SCOPES, 0, 1000, ATTN,
                                      exclude=LEAVES)
    ptc = engine_trace.layer_seconds(MADE_UP, SCOPES, 0, 1000,
                                     engine_trace.leaf_pattern(LEAVES))
    # fusion.9 of each step is absent; the loop is skipped
    assert attn == (pytest.approx(200e-9), 2)
    assert ptc == (pytest.approx(160e-9), 2)
    # under s0.attn with the PTC leaf counted too
    assert engine_trace.layer_seconds(MADE_UP, SCOPES, 0, 1000, ATTN)[0] == (
        pytest.approx(360e-9))

    def layer_of(op):
        return [name for name, pattern, exclude in (
            ("ptc", engine_trace.leaf_pattern(LEAVES), ()),
            ("attn", ATTN, LEAVES))
            if engine_trace.in_layer(op, pattern, exclude)]

    assert layer_of("a/s12.attn/wo/dot") == ["ptc"]
    assert layer_of("a/s1.attn/jit(prefill_attention)/x") == ["attn"]
    assert layer_of("a/s0.mlp/silu") == []
    assert layer_of("a/s0.attention/x") == []


HLO = r'''HloModule jit_gateway_step, is_scheduled=true

%fused_computation (param_0: f32[3]) -> f32[3] {
  %param_0 = f32[3]{0} parameter(0)
  ROOT %mul.0 = f32[3]{0} multiply(%param_0, %param_0), metadata={op_name="jit(gateway_step)/s0.attn/wq/mul" stack_frame_id=3}
}

%fused_computation.1 (param_0.1: f32[3]) -> f32[3] {
  %param_0.1 = f32[3]{0} parameter(0)
  ROOT %fusion.7 = f32[3]{0} fusion(%param_0.1), kind=kLoop, calls=%fused_computation
}

%fused_computation.2 (param_0.2: f32[3]) -> f32[3] {
  ROOT %param_0.2 = f32[3]{0} parameter(0)
}

ENTRY %main.16 (x.1: f32[3]) -> f32[3] {
  %x.1 = f32[3]{0} parameter(0), metadata={op_name="x[\"a\"]"}
  %fusion.1 = f32[3]{0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1
  fusion.2 = f32[3]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(gateway_step)/s0.mlp/up/mul"}
  %copy.3 = f32[3]{0} copy(fusion.2)
  ROOT %prefill_attention.3 = f32[3]{0} custom-call(%copy.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(gateway_step)/s0.attn/jit(prefill_attention)/pallas_call"}
}
'''


def test_op_scopes_from_hlo_text():
    got = engine_trace.op_scopes(HLO, "gateway_step")
    assert got["jit_gateway_step/fusion.1"] == (
        "jit(gateway_step)/s0.attn/wq/mul")       # through a nested fusion
    assert got["jit_gateway_step/fusion.2"] == (
        "jit(gateway_step)/s0.mlp/up/mul")        # its root has none
    assert got["jit_gateway_step/copy.3"] == ""
    assert got["jit_gateway_step/prefill_attention.3"].endswith(
        "s0.attn/jit(prefill_attention)/pallas_call")
    assert got["jit_gateway_step/x.1"] == 'x["a"]'
    assert all(k.startswith("jit_gateway_step/") for k in got)


def _ctx(**over):
    base = dict(cfg={}, mix={"slots": 4, "page_size": 16,
                             "max_pages_per_slot": 2},
                peaks={}, trace=MADE_UP, lo=0, hi=1000, window_s=1e-6,
                counts={"slots": 4, "busy_steps": 10, "slot_steps": 40,
                        "kv_live_positions": 800},
                scopes=FULL, ptc_leaves=LEAVES)
    base.update(over)
    return types.SimpleNamespace(**base)


def test_host_ms_reader():
    read = load_metric("host_ms.serve").read
    assert read(_ctx()) == pytest.approx(110e-6)
    assert read(_ctx(trace=tracing.Trace(
        ops=MADE_UP.ops, modules=MADE_UP.modules,
        spans=[["bench.traced", 0, 1000]]))) is None


def test_kv_view_live_reader():
    read = load_metric("kv_view_live.serve").read
    assert read(_ctx()) == pytest.approx(100 * 800 / (10 * 4 * 32))
    assert read(_ctx(counts={"slots": 4, "busy_steps": 10,
                             "slot_steps": 40})) is None


def test_attn_ms_reader():
    read = load_metric("attn_ms.serve").read
    assert read(_ctx()) == pytest.approx(100e-6)     # two runs
    assert read(_ctx(scopes=None)) is None
    # a program whose ops carry no layer scope reads as nothing
    assert read(_ctx(scopes={k: "jit(gateway_step)/dot_general"
                             for k in FULL})) is None
    # nor does a map that misses an op of the program that ran
    # (fusion.9), rather than read low
    assert engine_trace.absent_ops(MADE_UP, SCOPES, 0, 1000) == 2
    assert engine_trace.absent_ops(MADE_UP, FULL, 0, 1000) == 0
    assert read(_ctx(scopes=SCOPES)) is None


def test_ptc_ms_reader():
    read = load_metric("ptc_ms.serve").read
    assert read(_ctx()) == pytest.approx(80e-6)
    for left_out in ("scopes", "ptc_leaves"):
        assert read(types.SimpleNamespace(**{
            k: v for k, v in vars(_ctx()).items() if k != left_out})) is None


@pytest.mark.parametrize("chunk", [1, 4])
def test_step_scopes_map_the_program_the_engine_runs(chunk):
    """The map built from the engine's step function at the shapes of its
    views covers every instruction of the program it compiles for the
    views it gathers, with the same ``op_name``."""
    import jax
    import jax.numpy as jnp

    from repro.models.layers import PTCLinearCfg
    from repro.models.lm import ArchConfig, init_model
    from repro.serving import GatewayConfig, PageConfig, ServingGateway

    arch = ArchConfig(name="hwtest", family="dense", n_layers=2, d_model=32,
                      n_heads=2, n_kv_heads=1, d_ff=48, vocab=64,
                      head_dim=16, remat=False,
                      ptc=PTCLinearCfg(k=8, base_dtype=jnp.float32))
    params = init_model(jax.random.PRNGKey(5), arch)
    gw = ServingGateway(arch, params, GatewayConfig(
        slots=3, pages=PageConfig(page_size=4, n_pages=16,
                                  max_pages_per_slot=4),
        prefill_chunk=chunk))
    batch = {"token": jnp.zeros((3, chunk), jnp.int32),
             "lens": jnp.asarray(np.zeros(3, np.int32))}
    if chunk > 1:
        batch["n_valid"] = jnp.ones((3,), jnp.int32)
    ran = gw._step_fn.lower(params, gw._gather_views(), batch).compile()
    program = "prefill_step" if chunk > 1 else "gateway_step"
    want = engine_trace.op_scopes(ran.as_text(), program)
    got = engine_trace.step_scopes(gw)
    assert got == want
    leaves = engine_trace.ptc_leaves(params)
    assert leaves == LEAVES
    for pattern, exclude in ((ATTN, leaves),
                             (engine_trace.leaf_pattern(leaves), ())):
        assert any(engine_trace.in_layer(v, pattern, exclude)
                   for v in got.values())


def test_step_scopes_of_a_smoke_qwen_are_the_attn_cfg_views():
    """The engine's own step function at the views it gathers gives the
    map that a separate jit of the step gave at views taken from
    ``attn_cfg`` (k and v of n_kv_heads x head_dim, bf16)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import smoke_config
    from repro.models.lm import build_gateway_step, init_model, period_plan
    from repro.serving import GatewayConfig, PageConfig, ServingGateway

    arch = dataclasses.replace(smoke_config("qwen3-4b"), remat=False)
    params = init_model(jax.random.PRNGKey(3), arch)
    gw = ServingGateway(arch, params, GatewayConfig(
        slots=3, pages=PageConfig(page_size=4, n_pages=16,
                                  max_pages_per_slot=4)))
    plan, n_periods = period_plan(arch)
    views = {}
    for i, sub in enumerate(plan):
        acfg = arch.attn_cfg(sub.window)
        view = jax.ShapeDtypeStruct(
            (n_periods, 3, 16, acfg.n_kv_heads, acfg.head_dim), jnp.bfloat16)
        views[f"pos{i}"] = {"k": view, "v": view}
    batch = {"token": jax.ShapeDtypeStruct((3, 1), jnp.int32),
             "lens": jax.ShapeDtypeStruct((3,), jnp.int32)}
    want = engine_trace.op_scopes(jax.jit(build_gateway_step(arch)).lower(
        params, views, batch).compile().as_text(), "gateway_step")
    assert engine_trace.step_scopes(gw) == want


def test_a_ptc_leaf_of_another_name_counts_as_ptc():
    """Latent attention's down-projection ``wkv_a`` is a PTC leaf because
    the layout says so, not because a list names it."""
    f32 = np.float32
    factors = {"u": f32(0), "s": f32(0), "v": f32(0)}
    layout = {"embed": f32(0), "pos0": {
        "attn": {"wkv_a": factors, "wq": factors, "norm": {"g": f32(0)}},
        "moe": {"router": f32(0), "experts": {"up": factors}}}}
    leaves = engine_trace.ptc_leaves(layout)
    assert leaves == {"wkv_a", "wq", "up"}
    scopes = dict(FULL, **{"jit_gateway_step/fusion.1":
                             "jit(gateway_step)/s0.attn/wkv_a/dot_general"})
    read = load_metric("ptc_ms.serve").read
    # fusion.1 (100 ns a step) now lies under a PTC leaf, beside fusion.2
    assert read(_ctx(scopes=scopes, ptc_leaves=leaves)) == pytest.approx(
        180e-6)
    assert load_metric("attn_ms.serve").read(
        _ctx(scopes=scopes, ptc_leaves=leaves)) is None


def test_layer_ms_reads_a_scope_of_another_pattern():
    """A reader of a layer the dense cells do not have (``s{i}.moe``)
    reads its own pattern from the same trace and scope map."""
    moe = dict(FULL, **{
        "jit_gateway_step/fusion.2":
            "jit(gateway_step)/while/body/closed_call/s3.moe/up/dot_general",
        "jit_gateway_step/copy.3": "jit(gateway_step)/s3.moe/sort"})
    moe_pattern = r"^s\d+\.moe$"
    # fusion.2 (80 ns a step, under a PTC leaf) and copy.3 (15 ns)
    assert engine_trace.layer_ms(_ctx(scopes=moe), moe_pattern) == (
        pytest.approx(95e-6))
    assert engine_trace.layer_ms(_ctx(scopes=moe), moe_pattern,
                                 exclude_ptc=True) == pytest.approx(15e-6)
    # the dense map has no such scope: nothing to read
    assert engine_trace.layer_ms(_ctx(), moe_pattern) is None


def test_a_counter_the_gateway_adds_reaches_its_reader(tmp_path):
    """Every public integer attribute of the gateway reaches
    ``ctx.counts`` as its difference across the traced span: one that a
    later engine adds is read by a reader of its own, with no edit."""
    from bench.entries.gateway import (MetricContext, counts_across,
                                       engine_counters)

    gw = types.SimpleNamespace(step_count=7, busy_steps=5, slot_steps=20,
                               kv_live_positions=300, tokens_out=9,
                               chunk=32, preempted=np.int64(2),
                               _scratch=4, closed=False, name="gw")
    before = engine_counters(gw)
    assert set(before) == {"step_count", "busy_steps", "slot_steps",
                           "kv_live_positions", "tokens_out", "chunk",
                           "preempted"}
    gw.busy_steps, gw.slot_steps, gw.preempted = 15, 60, np.int64(5)
    gw.kv_live_positions = 1100
    counts = counts_across(before, engine_counters(gw), slots=4)
    assert counts["preempted"] == 3 and counts["chunk"] == 0
    assert counts["slots"] == 4 and "_scratch" not in counts
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "preempted.serve.py").write_text(
        "def read(ctx):\n    return ctx.counts.get('preempted')\n")
    ctx = MetricContext(cfg={}, mix={"slots": 4, "page_size": 16,
                                     "max_pages_per_slot": 2},
                        peaks={}, trace=MADE_UP, lo=0, hi=1000,
                        window_s=1e-6, counts=counts)
    assert load_metric("preempted.serve", bench=tmp_path).read(ctx) == 3
    # a counter named as one of the harness's counts would hide it
    gw.slots = 4
    with pytest.raises(ValueError, match="slots"):
        counts_across(before, engine_counters(gw), slots=4)
    assert load_metric("kv_view_live.serve").read(ctx) == pytest.approx(
        100 * 800 / (10 * 4 * 32))
    assert load_metric("occupancy.serve").read(ctx) == pytest.approx(100.0)
