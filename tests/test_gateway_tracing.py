"""What the serving gateway tells a profiler: the engine's step and phase
spans, its count of live KV positions, and the named scopes that reach
the step program's HLO ``op_name`` metadata."""

import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, smoke_config
from repro.models.layers import PTCLinearCfg
from repro.models.lm import (ArchConfig, build_gateway_prefill_step,
                             build_gateway_step, build_serve_step,
                             init_decode_cache, init_model, period_plan)
from repro.runtime.hw_serve import record_ptc_layers
from repro.serving import GatewayConfig, PageConfig, Request, ServingGateway

ARCH = ArchConfig(name="hwtest", family="dense", n_layers=2, d_model=32,
                  n_heads=2, n_kv_heads=1, d_ff=48, vocab=64, head_dim=16,
                  remat=False, ptc=PTCLinearCfg(k=8, base_dtype=jnp.float32))
PARAMS = init_model(jax.random.PRNGKey(5), ARCH)
PHASES = ["gateway.admit", "gateway.stage", "gateway.gather",
          "gateway.forward", "gateway.scatter", "gateway.fetch",
          "gateway.emit"]
LEAVES = ["wq", "wk", "wv", "wo", "gate", "up", "down"]


def _gateway(chunk: int, slots: int = 3) -> ServingGateway:
    # S_max 16: a multiple of 8 that the prefill kernel can stream through
    gcfg = GatewayConfig(slots=slots,
                         pages=PageConfig(page_size=4, n_pages=32,
                                          max_pages_per_slot=4),
                         prefill_chunk=chunk)
    return ServingGateway(ARCH, PARAMS, gcfg)


def _requests(arrivals=(0, 0, 0, 0, 0), seed=3):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, arrival=a, max_new=int(rng.integers(1, 5)),
                    prompt=rng.integers(0, ARCH.vocab, int(rng.integers(
                        1, 10))).astype(np.int32))
            for i, a in enumerate(arrivals)]


@pytest.fixture
def recorded(monkeypatch):
    """Stands ``jax.profiler``'s span constructors in with a recorder of
    each span's entry and exit, with its arguments."""
    log = []

    class Span:
        def __init__(self, name, **kwargs):
            self.name, self.kwargs = name, kwargs

        def __enter__(self):
            log.append(("enter", self.name, self.kwargs))

        def __exit__(self, *exc):
            log.append(("exit", self.name, self.kwargs))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Span)
    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", Span)
    return log


@pytest.mark.parametrize("chunk", [1, 4])
def test_each_busy_step_is_one_span_of_seven_phases(recorded, chunk):
    gw = _gateway(chunk, slots=2)
    # gaps in the arrivals leave idle steps, which get no span
    gw.run(_requests(arrivals=(0, 0, 30, 31, 60)))
    assert gw.busy_steps < gw.step_count
    steps = []
    it = iter(recorded)
    for kind, name, kw in it:
        assert (kind, name) == ("enter", "gateway.step")
        assert kw.get("step_num") is not None
        inner = []
        for kind, name, _ in it:
            if name == "gateway.step":
                assert kind == "exit"
                break
            inner.append((kind, name))
        assert inner == [(k, p) for p in PHASES for k in ("enter", "exit")]
        steps.append(kw["step_num"])
    assert len(steps) == gw.busy_steps
    assert steps == sorted(set(steps))
    assert steps[0] == 0 and steps[-1] == gw.step_count - 1


def _live_positions(prompt_len: int, max_new: int, chunk: int) -> int:
    """Σ over a request's steps of the positions its attention covers:
    each prefill chunk's end, then one more position a decode step."""
    ends = list(range(chunk, prompt_len, chunk)) + [prompt_len]
    return sum(ends) + sum(range(prompt_len + 1, prompt_len + max_new))


@pytest.mark.parametrize("chunk", [1, 4])
def test_kv_live_positions_from_request_lengths(chunk):
    gw = _gateway(chunk)
    reqs = _requests()
    gw.run(reqs)
    assert all(len(r.out_tokens) == r.max_new for r in reqs)
    assert gw.kv_live_positions == sum(
        _live_positions(r.prompt_len, r.max_new, chunk) for r in reqs)


@pytest.mark.parametrize("chunk", [1, 4])
def test_served_tokens_same_with_a_profile_recorded(tmp_path, chunk):
    plain = _gateway(chunk).run(_requests())
    gw = _gateway(chunk)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = gw.run(_requests())
    finally:
        jax.profiler.stop_trace()
    assert [r["tokens"] for r in traced["requests"]] == [
        r["tokens"] for r in plain["requests"]]
    # the spans land in the profile under their own names
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    names = [e.name
             for plane in jax.profiler.ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("gateway.")]
    assert names.count("gateway.step") == gw.busy_steps
    for phase in PHASES:
        assert names.count(phase) == gw.busy_steps


def _step_op_names(chunk: int) -> set[str]:
    gw = _gateway(chunk)
    views = gw._gather_views()
    batch = {"token": jnp.zeros((3, chunk), jnp.int32),
             "lens": jnp.zeros((3,), jnp.int32)}
    if chunk > 1:
        batch["n_valid"] = jnp.ones((3,), jnp.int32)
    text = gw._step_fn.lower(gw.params, views, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("chunk", [1, 4])
def test_step_hlo_carries_the_layer_scopes(chunk):
    scopes = {part for name in _step_op_names(chunk)
              for part in name.split("/")}
    for want in ["s0.attn", "s0.mlp", *LEAVES]:
        assert want in scopes, (want, sorted(scopes))
    # every PTC leaf sits under its sub-layer's scope
    for name in _step_op_names(chunk):
        parts = name.split("/")
        for leaf in set(LEAVES) & set(parts):
            owner = "s0.attn" if leaf in LEAVES[:4] else "s0.mlp"
            assert owner in parts[:parts.index(leaf)], name


def test_ptc_layer_names_unchanged():
    cfg = dataclasses.replace(ARCH, unroll=True)
    serve = build_serve_step(cfg)
    cache = init_decode_cache(cfg, 2, 4)
    batch = {"token": jnp.zeros((2, 1), jnp.int32),
             "cache_len": jnp.asarray(0, jnp.int32)}
    names = [s.name for s in record_ptc_layers(serve, PARAMS, cache, batch)]
    assert names == [f"p{p}.s0.{mod}.{leaf}" for p in range(2)
                     for mod, leaves in (("attn", LEAVES[:4]),
                                         ("mlp", LEAVES[4:]))
                     for leaf in leaves]


def _scope_sequence(text: str) -> list[tuple[str | None, str]]:
    """(period, sub-layer) scope pairs in the order the ops of a lowered
    program's ``as_text(debug_info=True)`` first carry them."""
    locs = dict(re.findall(r'^#(loc\d*) = loc\("([^"]*)"', text, re.M))
    seen = []
    for ref in re.findall(r' loc\(#(loc\d*)\)$', text, re.M):
        parts = locs.get(ref, "").split("/")
        period = next((x for x in parts if re.fullmatch(r"p\d+", x)), None)
        sub = next((x for x in parts
                    if re.fullmatch(r"s\d+\.[a-z]+", x)), None)
        if sub is not None and (period, sub) not in seen:
            seen.append((period, sub))
    return seen


@pytest.mark.parametrize("unroll", [False, True])
@pytest.mark.parametrize("name", ARCH_NAMES)
def test_serving_programs_carry_the_plan_scopes(name, unroll):
    """The solo serve step, the one-token gateway step and the C=4
    prefill step scope every sub-layer the period plan holds, in plan
    order, in every period (one scan body when not unrolled); the MoE
    call is unscoped."""
    cfg = dataclasses.replace(smoke_config(name), unroll=unroll)
    plan, n_periods = period_plan(cfg)
    b, s = 2, 16
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    views = jax.eval_shape(lambda: init_decode_cache(cfg, b, s))
    tok = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32)
    solo = {"token": tok, "cache_len": jax.ShapeDtypeStruct((), jnp.int32)}
    if cfg.family == "vlm":
        solo["img"] = jax.ShapeDtypeStruct((b, cfg.n_img_tokens,
                                            cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        solo["enc_out"] = jax.ShapeDtypeStruct((b, s, cfg.d_model),
                                               jnp.float32)
    programs = {"serve_step": (build_serve_step, solo)}
    if cfg.family not in ("vlm", "encdec") and not cfg.n_experts:
        programs["gateway_step"] = (build_gateway_step,
                                    {"token": tok, "lens": lens})
        if all(sub.kind == "attn" for sub in plan):
            programs["prefill_step"] = (build_gateway_prefill_step, {
                "token": jax.ShapeDtypeStruct((b, 4), jnp.int32),
                "lens": lens, "n_valid": lens})
    per_period = [f"s{i}.{mod}" for i, sub in enumerate(plan)
                  for mod in (sub.kind, "cross" if sub.cross else None,
                              sub.ffn if sub.ffn == "mlp" else None)
                  if mod is not None]
    want = [(f"p{pi}" if unroll else None, scope)
            for pi in range(n_periods if unroll else 1)
            for scope in per_period]
    for prog, (build, batch) in programs.items():
        text = jax.jit(build(cfg)).lower(params, views, batch).as_text(
            debug_info=True)
        assert _scope_sequence(text) == want, prog
