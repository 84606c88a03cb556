"""Decode attention reads K/V by KV-head group, in place.

* **Equivalence**: ``decode_attention_paged`` (the gateway path) and
  ``decode_attention`` (the dense solo path) match a float32 reference
  written the plain way — the new row spliced into K/V, K/V repeated to
  every query head, a masked softmax over the spliced length — for
  several query heads per KV head, with and without a sliding window
  and soft-capping, and view rows past each slot's length filled with
  garbage that only the mask keeps out.
* **Structure**: the traced gateway step holds no per-head copy of a
  view ``(…, S_max, n_heads, Dh)`` and writes nothing into an
  S_max-long view, so the views are read once, as gathered.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import (NEG_INF, AttnCfg, _project_qkv,
                                    decode_attention, decode_attention_paged,
                                    init_attention)
from repro.models.layers import PTCLinearCfg, apply_ptc_linear, softcap
from repro.models.lm import ArchConfig, build_gateway_step, init_model

LIN = PTCLinearCfg(k=8)
B, S, HKV, DH = 4, 12, 2, 8
LENS = (0, 3, 9, S - 1)
GARBAGE = 1e3


def _cfg(rep, window, cap):
    return AttnCfg(d_model=32, n_heads=HKV * rep, n_kv_heads=HKV,
                   head_dim=DH, window=window, attn_softcap=cap)


def _inputs(valid, seed=0):
    """x (B, 1, d) and bf16 views whose rows at ``~valid`` (B, S) are
    garbage: large enough to swamp the result if the mask let them in."""
    kx, kk, kv, kg = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(kx, (B, 1, 32), jnp.bfloat16)
    junk = GARBAGE * jnp.sign(jax.random.normal(kg, (B, S, HKV, DH)))
    keep = valid[:, :, None, None]
    k = jnp.where(keep, jax.random.normal(kk, (B, S, HKV, DH)), junk)
    v = jnp.where(keep, jax.random.normal(kv, (B, S, HKV, DH)), -junk)
    return x, k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)


def _reference(p, cfg, q, k, v, ok):
    """float32 attention over spliced K/V repeated to every query head;
    ok: (B, S) the keys each slot attends.  Returns the layer output."""
    f32 = lambda a: a.astype(jnp.float32)
    rep = cfg.n_heads // cfg.n_kv_heads
    kr, vr = jnp.repeat(f32(k), rep, axis=2), jnp.repeat(f32(v), rep, axis=2)
    logits = jnp.einsum("bqhd,bkhd->bhqk", f32(q), kr) * cfg.head_dim ** -0.5
    logits = softcap(logits, cfg.attn_softcap)
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(logits, -1), vr)
    o = o.reshape(B, 1, cfg.n_heads * cfg.head_dim).astype(q.dtype)
    return apply_ptc_linear(p["wo"], o, LIN, d_out=cfg.d_model, name="wo")


def _window_ok(ki, last, window):
    """Keys 0..last, inside the window ending at ``last``."""
    ok = ki <= last
    if window is not None:
        ok = ok & (ki > last - window)
    return ok


def _close(got, want):
    # bf16 activations, views and weights, rounded where the reference
    # keeps float32: four bf16 ulps of the output's scale
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=4 * 2.0 ** -8 * scale)


CASES = [(rep, window, cap) for rep in (1, 2, 4)
         for window, cap in ((None, None), (5, None), (None, 30.0), (5, 30.0))]


@pytest.mark.parametrize("rep,window,cap", CASES)
def test_paged_decode_matches_repeat_and_splice_reference(rep, window, cap):
    cfg = _cfg(rep, window, cap)
    p = init_attention(jax.random.PRNGKey(1), cfg, LIN)
    lens = jnp.asarray(LENS, jnp.int32)
    ki = jnp.arange(S)[None, :]
    x, k_view, v_view = _inputs(ki < lens[:, None])
    out, k_new, v_new = jax.jit(
        lambda *a: decode_attention_paged(p, cfg, LIN, *a))(
            x, k_view, v_view, lens)

    q, k_ref, v_ref = _project_qkv(p, cfg, LIN, x, lens[:, None])
    splice = jax.vmap(lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(
        c, n, i, axis=0))
    k = splice(k_view, k_ref.astype(k_view.dtype), lens)
    v = splice(v_view, v_ref.astype(v_view.dtype), lens)
    want = _reference(p, cfg, q, k, v, _window_ok(ki, lens[:, None], window))

    assert out.shape == (B, 1, cfg.d_model) and out.dtype == x.dtype
    _close(out, want)
    np.testing.assert_array_equal(np.asarray(k_new), np.asarray(k_ref))
    np.testing.assert_array_equal(np.asarray(v_new), np.asarray(v_ref))


@pytest.mark.parametrize("rep,window,cap", CASES)
def test_dense_decode_matches_repeat_reference(rep, window, cap):
    cfg = _cfg(rep, window, cap)
    p = init_attention(jax.random.PRNGKey(1), cfg, LIN)
    cache_len = 7
    ki = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x, k_cache, v_cache = _inputs(ki < cache_len)
    out, cache = jax.jit(lambda x, c: decode_attention(
        p, cfg, LIN, x, c, cache_len))(x, {"k": k_cache, "v": v_cache})

    positions = jnp.full((B, 1), cache_len, jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, LIN, x, positions)
    k = k_cache.at[:, cache_len].set(k_new[:, 0].astype(k_cache.dtype))
    v = v_cache.at[:, cache_len].set(v_new[:, 0].astype(v_cache.dtype))
    want = _reference(p, cfg, q, k, v, _window_ok(ki, cache_len, window))

    _close(out, want)
    np.testing.assert_array_equal(np.asarray(cache["k"]), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(cache["v"]), np.asarray(v))


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold
    (the scan body, jitted calls, custom derivatives)."""
    for e in jaxpr.eqns:
        yield e
        for val in e.params.values():
            for sub in val if isinstance(val, (list, tuple)) else (val,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("n_heads", [4, 8])
def test_gateway_step_reads_views_by_group_without_splice(n_heads):
    s_max, hkv, dh = 40, 2, 8
    cfg = ArchConfig(name="gqa", family="dense", n_layers=2, d_model=32,
                     n_heads=n_heads, n_kv_heads=hkv, d_ff=48, vocab=64,
                     head_dim=dh, remat=False, qk_norm=True, ptc=LIN)
    params = jax.eval_shape(lambda: init_model(jax.random.PRNGKey(0), cfg))
    view = jax.ShapeDtypeStruct((cfg.n_layers, 3, s_max, hkv, dh),
                                jnp.bfloat16)
    batch = {"token": jax.ShapeDtypeStruct((3, 1), jnp.int32),
             "lens": jax.ShapeDtypeStruct((3,), jnp.int32)}
    jaxpr = jax.make_jaxpr(build_gateway_step(cfg))(
        params, {"pos0": {"k": view, "v": view}}, batch).jaxpr

    read, repeated, spliced = 0, [], []
    for e in _eqns(jaxpr):
        ins = [getattr(v, "aval", None) for v in e.invars]
        shapes = [a.shape for a in ins if hasattr(a, "shape")]
        read += any(s[-3:] == (s_max, hkv, dh) for s in shapes)
        for out in e.outvars:
            if tuple(out.aval.shape[-3:]) == (s_max, n_heads, dh):
                repeated.append(e.primitive.name)
            if (e.primitive.name in ("dynamic_update_slice", "scatter",
                                     "scatter-add", "select_n")
                    and tuple(out.aval.shape[-3:]) == (s_max, hkv, dh)):
                spliced.append(e.primitive.name)
    assert read, "the walk never reached the attention over the views"
    assert not repeated, f"a per-head copy of a view: {repeated}"
    assert not spliced, f"a write into an S_max-long view: {spliced}"
