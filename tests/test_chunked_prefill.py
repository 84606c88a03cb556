"""Chunked paged prefill: kernel conformance + end-to-end identity.

Guarantee structure:

* **Kernel**: the Pallas online-softmax prefill attention matches a
  dense masked-softmax reference for every block size / window /
  soft-cap combination, and rerunning it is bitwise deterministic.
* **Pool spans**: ``write_span`` splits page-boundary-crossing chunks
  against the page table exactly and refuses to write past a
  reservation.
* **Bitwise KV property** (hypothesis): at a FIXED padded chunk width
  C, advancing ``stride`` tokens per step produces a page pool
  bit-identical to advancing one token per step — for random prompt
  lengths, chunk widths, page sizes and kernel KV blocks, including
  chunks straddling page boundaries and prompts shorter than one
  chunk.  (XLA:CPU matmul rows are position-invariant at fixed shape
  but NOT invariant across shapes, so bit-identity is defined at equal
  width; vs the (B, 1)-shaped legacy path the gate is token identity,
  the same relation the legacy path itself bears to sequential serve.)
* **Token identity**: chunked prefill (C>1) emits exactly the legacy
  path's tokens — digitally under mixed prefill+decode multi-request
  schedules, and through the hardware-in-the-loop twin transport with
  wide compacted frames (σ_drift = 0).  The socket-transport leg rides
  in ``benchmarks/serving_gateway.py`` (gated in the artifact).
"""

import argparse
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from tests._hypothesis_shim import given, settings, strategies as st

from repro.models.layers import PTCLinearCfg
from repro.models.lm import (ArchConfig, build_gateway_prefill_step,
                             init_model)
from repro.serving import (GatewayConfig, PageConfig, PagedKVPool, Request,
                           ServingGateway)

ARCH = ArchConfig(name="hwtest", family="dense", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=1, d_ff=48, vocab=64, head_dim=16,
                  remat=False,
                  ptc=PTCLinearCfg(k=8, base_dtype=jnp.float32))
PARAMS = init_model(jax.random.PRNGKey(5), ARCH)


# ---------------------------------------------------------------------------
# kernel conformance
# ---------------------------------------------------------------------------


def _reference(lens, q, k, v, window=None, cap=None):
    b, c, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    kr = np.repeat(np.asarray(k, np.float64), h // hkv, axis=2)
    vr = np.repeat(np.asarray(v, np.float64), h // hkv, axis=2)
    out = np.zeros((b, c, h, hd))
    for bb in range(b):
        for cc in range(c):
            qi = int(lens[bb]) + cc
            lg = np.einsum("hd,khd->hk", np.asarray(q, np.float64)[bb, cc],
                           kr[bb]) * hd ** -0.5
            if cap is not None:
                lg = cap * np.tanh(lg / cap)
            ki = np.arange(s)
            ok = ki <= qi
            if window is not None:
                ok &= ki > qi - window
            lg = np.where(ok[None], lg, -np.inf)
            w = np.exp(lg - lg.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[bb, cc] = np.einsum("hk,khd->hd", w, vr[bb])
    return out


@pytest.mark.parametrize("blk", [None, 8, 4])
@pytest.mark.parametrize("window,cap", [(None, None), (6, None),
                                        (None, 3.0), (5, 2.0)])
def test_prefill_kernel_matches_dense_reference(blk, window, cap):
    from repro.kernels.prefill_attn import prefill_attention

    rng = np.random.default_rng(0)
    b, c, h, hkv, hd, s = 3, 5, 4, 2, 8, 24
    lens = jnp.asarray([0, 7, 19], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    got = prefill_attention(lens, q, k, v, blk=blk, window=window, cap=cap,
                            interpret=True)
    want = _reference(lens, q, k, v, window=window, cap=cap)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5)
    again = prefill_attention(lens, q, k, v, blk=blk, window=window, cap=cap,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(again))


def test_prefill_kernel_fully_masked_block_is_exact_zero():
    """A KV block entirely outside the causal window must contribute
    exactly nothing — the masked-exp discipline, not just allclose."""
    from repro.kernels.prefill_attn import prefill_attention

    rng = np.random.default_rng(1)
    b, c, h, hkv, hd, s = 1, 2, 2, 1, 4, 16
    lens = jnp.asarray([12], jnp.int32)
    q = jnp.asarray(rng.normal(size=(b, c, h, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, hd)), jnp.float32)
    base = prefill_attention(lens, q, k, v, blk=4, window=3, interpret=True)
    # rewrite the keys/values the window can never see; output unchanged
    k2 = k.at[:, :8].set(999.0)
    v2 = v.at[:, :8].set(-999.0)
    poked = prefill_attention(lens, q, k2, v2, blk=4, window=3,
                              interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poked))


@pytest.mark.parametrize("s,c,h,hkv,hd,itemsize,want", [
    (1024, 32, 32, 8, 128, 2, 128),   # qwen3-4b chunk over a 1024 view
    (8192, 32, 32, 8, 128, 2, 128),
    (1000, 32, 32, 8, 128, 2, 200),   # largest fitting divisor
    (24, 5, 4, 2, 8, 4, 24),          # small views stay one block
])
def test_prefill_kv_block_is_derived_from_shapes(s, c, h, hkv, hd, itemsize,
                                                 want):
    """The derived block divides the view, is a multiple of 8 or the
    whole view, and keeps one grid step inside the VMEM budget."""
    from repro.kernels import prefill_attn

    blk = prefill_attn.kv_block(s, c, h, hkv, hd, itemsize)
    assert blk == want
    assert s % blk == 0 and (blk % 8 == 0 or blk == s)
    assert (prefill_attn._step_bytes(blk, c, h, hkv, hd, itemsize)
            <= prefill_attn.VMEM_BUDGET)


def test_prefill_kernel_rejects_indivisible_block():
    from repro.kernels.prefill_attn import prefill_attention

    with pytest.raises(ValueError, match="not divisible"):
        prefill_attention(jnp.zeros((1,), jnp.int32),
                          jnp.zeros((1, 2, 2, 4), jnp.float32),
                          jnp.zeros((1, 10, 1, 4), jnp.float32),
                          jnp.zeros((1, 10, 1, 4), jnp.float32), blk=4,
                          interpret=True)


# ---------------------------------------------------------------------------
# pool write spans
# ---------------------------------------------------------------------------


def test_write_span_splits_page_boundaries():
    cfg = PageConfig(page_size=4, n_pages=8, max_pages_per_slot=3)
    pool = PagedKVPool(cfg, 1)
    pool.reserve(0, 10)
    pool.advance(0, 3)                     # next position: page 0, off 3
    span = pool.write_span(0, 6)           # crosses 0→1 and 1→...
    pages = pool.table[0]
    want = np.asarray([[pages[0], 3], [pages[1], 0], [pages[1], 1],
                       [pages[1], 2], [pages[1], 3], [pages[2], 0]],
                      np.int32)
    np.testing.assert_array_equal(span, want)
    # one-row span degenerates to write_pos
    assert tuple(pool.write_span(0, 1)[0]) == pool.write_pos(0)


def test_write_span_refuses_past_reservation():
    cfg = PageConfig(page_size=4, n_pages=8, max_pages_per_slot=3)
    pool = PagedKVPool(cfg, 1)
    pool.reserve(0, 6)                     # 2 pages
    pool.advance(0, 5)
    with pytest.raises(RuntimeError, match="past its reservation"):
        pool.write_span(0, 4)
    assert pool.write_span(0, 3).shape == (3, 2)


# ---------------------------------------------------------------------------
# bitwise KV + token identity properties
# ---------------------------------------------------------------------------


def _run_single(prompt_len, max_new, chunk, stride, page_size, seed=9):
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=0, prompt=rng.integers(
        0, ARCH.vocab, size=(prompt_len,)).astype(np.int32),
        max_new=max_new, arrival=0)]
    # the view S_max is a multiple of 8 and at least 16, so it has a KV
    # block of 8 smaller than itself for the kernel to stream through
    s_max = -(-max(16, prompt_len + max_new) // 8) * 8
    gcfg = GatewayConfig(
        slots=2,
        pages=PageConfig(page_size=page_size, n_pages=24,
                         max_pages_per_slot=s_max // page_size),
        prefill_chunk=chunk, prefill_stride=stride)
    gw = ServingGateway(ARCH, PARAMS, gcfg)
    rep = gw.run(reqs)
    stripe = gcfg.pages.n_pages + 1
    keep = np.asarray([r for r in range(gw.n_periods * stripe)
                       if r % stripe != gcfg.pages.n_pages])
    pools = {f"{n}.{kk}": np.asarray(p[kk])[keep]
             for n, p in gw._pools.items() for kk in ("k", "v")}
    return rep["requests"][0]["tokens"], pools


@settings(max_examples=6, deadline=None)
@given(prompt_len=st.integers(1, 22), chunk=st.sampled_from([2, 3, 5, 8]),
       stride=st.integers(1, 8), page_size=st.sampled_from([2, 4, 8]),
       kv_blk=st.sampled_from([None, 8]))
def test_chunked_prefill_bitwise_kv_and_token_identity(
        prompt_len, chunk, stride, page_size, kv_blk):
    """stride s ≤ C at padded width C is bit-identical in pool contents
    and tokens to stride C at width C; both emit the legacy one-token
    path's tokens.  ``kv_blk`` 8 shrinks the kernel's VMEM budget to one
    block of 8 rows, so the derived block is smaller than the view and
    the online softmax accumulates over several blocks; None keeps the
    default budget, under which the whole view is one block."""
    from repro.kernels import prefill_attn

    stride = min(stride, chunk)
    max_new = 3
    budget = prefill_attn.VMEM_BUDGET
    if kv_blk is not None:
        budget = prefill_attn._step_bytes(kv_blk, chunk, ARCH.n_heads,
                                          ARCH.n_kv_heads, ARCH.head_dim, 4)
    derived, kv_block = [], prefill_attn.kv_block

    def spy(s, *dims):
        derived.append((s, kv_block(s, *dims)))
        return derived[-1][1]

    # traces cached under another budget would skip the derivation
    jax.clear_caches()
    with mock.patch.object(prefill_attn, "VMEM_BUDGET", budget), \
            mock.patch.object(prefill_attn, "kv_block", spy):
        tok_c, pool_c = _run_single(prompt_len, max_new, chunk, None,
                                    page_size)
        tok_s, pool_s = _run_single(prompt_len, max_new, chunk, stride,
                                    page_size)
    jax.clear_caches()
    tok_1, _ = _run_single(prompt_len, max_new, 1, None, page_size)
    assert derived, "the prefill kernel was not traced"
    if kv_blk is None:
        assert all(blk == s for s, blk in derived), derived
    else:
        assert all(blk % 8 == 0 and blk < s for s, blk in derived), derived
    assert tok_c == tok_s == tok_1
    assert pool_c.keys() == pool_s.keys() and len(pool_c) > 0
    for name in pool_c:
        np.testing.assert_array_equal(pool_c[name], pool_s[name],
                                      err_msg=f"{name} diverged bitwise")


def test_chunked_mixed_prefill_decode_token_identical_to_legacy():
    """Multi-request schedule: chunked steps mix prefilling slots
    (n_valid up to C) with decoding slots (n_valid == 1) and still emit
    the legacy path's tokens, in fewer busy steps."""
    def run(chunk):
        rng = np.random.default_rng(7)
        reqs = [Request(rid=i, prompt=rng.integers(
            0, ARCH.vocab, size=(ln,)).astype(np.int32),
            max_new=mn, arrival=ar)
            for i, (ln, mn, ar) in enumerate(
                [(11, 3, 0), (15, 4, 1), (5, 3, 2), (14, 3, 4)])]
        gcfg = GatewayConfig(
            slots=3, pages=PageConfig(page_size=4, n_pages=40,
                                      max_pages_per_slot=8),
            prefill_chunk=chunk)
        gw = ServingGateway(ARCH, PARAMS, gcfg)
        rep = gw.run(reqs)
        return [r["tokens"] for r in rep["requests"]], rep

    tok_1, rep_1 = run(1)
    tok_8, rep_8 = run(8)
    assert tok_8 == tok_1
    assert rep_8["busy_steps"] < rep_1["busy_steps"]
    assert rep_8["ttft_steps"]["p50"] < rep_1["ttft_steps"]["p50"]
    assert all(r["first_token"] >= 0 for r in rep_8["requests"])


def test_chunked_prefill_hw_twin_token_identical_with_wide_frames():
    """Hardware-in-the-loop chunked prefill (twin transport, σ=0):
    tokens match the one-token hw path, frames drop, and each wide
    frame ships only the valid (compacted) activation columns."""
    from repro.serving.gateway import run as gw_run

    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, ARCH.vocab, size=(int(rng.integers(6, 14)),)).astype(np.int32),
        max_new=2, arrival=i) for i in range(3)]
    params = init_model(jax.random.PRNGKey(5),
                        dataclasses.replace(ARCH, unroll=True, remat=False))

    def args(**over):
        base = dict(arch=ARCH, seed=5, slots=3, requests=len(reqs),
                    rate=1.0, page_size=4, pages=24, max_pages_per_slot=4,
                    max_new=(2, 4), eos_id=None, fleet=2, drift=False,
                    drift_sigma=0.0, probe_every=4, fleet_k=8,
                    fleet_driver="twin", hw_logits=True, hw_shadow=False,
                    deploy_zo=False, no_recal=True,
                    params_override=params,
                    requests_override=[dataclasses.replace(r, out_tokens=[])
                                       for r in reqs])
        base.update(over)
        return argparse.Namespace(**base)

    rep_1 = gw_run(args())
    rep_4 = gw_run(args(prefill_chunk=4))
    assert ([r["tokens"] for r in rep_4["requests"]]
            == [r["tokens"] for r in rep_1["requests"]])
    hw_1, hw_4 = rep_1["fleet"]["hw"], rep_4["fleet"]["hw"]
    assert hw_4["frames"] < hw_1["frames"]
    # coalescing untouched: still one frame per layer group per step
    assert hw_4["frames_per_step"] == hw_1["frames_per_step"] == 4.0
    # wide frames really carry >1 column/slot on average, but fewer than
    # the uncompacted B·C — the valid-mask compaction is live
    assert hw_1["cols_per_frame"] <= 3.0
    assert 3.0 < hw_4["cols_per_frame"] < 12.0


def test_prefill_step_refuses_non_attention_archs():
    ssm = ArchConfig(name="s", family="ssm", n_layers=2, d_model=16,
                     n_heads=2, n_kv_heads=1, d_ff=16, vocab=32,
                     ssm_state=4)
    with pytest.raises(ValueError, match="attention-only"):
        build_gateway_prefill_step(ssm)
    moe = dataclasses.replace(ARCH, n_experts=4, top_k=2)
    with pytest.raises(ValueError, match="MoE"):
        build_gateway_prefill_step(moe)
