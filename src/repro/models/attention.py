"""GQA self/cross attention over PTC-factorized projections.

Features needed across the assigned archs: grouped KV heads (all),
qk-norm (qwen3), attention/logit soft-capping (gemma2), sliding-window
local layers (gemma2 alternates local/global), partial/2d rotary
(chatglm), cross-attention (whisper decoder, llama-vision), KV-cache
decode (serve path), and chunked-softmax attention for long prefill
(online softmax over KV blocks — memory O(S·chunk) instead of O(S²)).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from ..kernels.ops import prefill_attention
from .layers import (PTCLinearCfg, init_ptc_linear, apply_ptc_linear,
                     init_rmsnorm, rmsnorm, rotary_cache, apply_rotary,
                     softcap)

__all__ = ["AttnCfg", "init_attention", "attention", "decode_attention",
           "decode_attention_paged", "decode_attention_paged_chunked",
           "init_kv_cache"]

Params = dict[str, Any]
NEG_INF = -2.0 ** 30


@dataclasses.dataclass(frozen=True)
class AttnCfg:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    rope_frac: float = 1.0          # <1 = partial rotary (chatglm 2d-RoPE)
    qk_norm: bool = False           # qwen3
    attn_softcap: float | None = None   # gemma2
    qkv_bias: bool = False          # chatglm3
    causal: bool = True             # False for encoder / cross-attn
    window: int | None = None       # sliding window (gemma2 local layers)


def init_attention(key: jax.Array, cfg: AttnCfg, lin: PTCLinearCfg) -> Params:
    kq, kk, kv, ko, kn = jax.random.split(key, 5)
    d, hd = cfg.d_model, cfg.head_dim
    p: Params = {
        "wq": init_ptc_linear(kq, d, cfg.n_heads * hd, lin, bias=cfg.qkv_bias),
        "wk": init_ptc_linear(kk, d, cfg.n_kv_heads * hd, lin,
                              bias=cfg.qkv_bias),
        "wv": init_ptc_linear(kv, d, cfg.n_kv_heads * hd, lin,
                              bias=cfg.qkv_bias),
        "wo": init_ptc_linear(ko, cfg.n_heads * hd, d, lin),
    }
    if cfg.qk_norm:
        p["qn"] = init_rmsnorm(hd)
        p["kn"] = init_rmsnorm(hd)
    return p


def _project_qkv(p: Params, cfg: AttnCfg, lin: PTCLinearCfg, x, positions,
                 kv_x=None):
    """Project (and rope/norm) q from x, k/v from kv_x (defaults to x)."""
    b = x.shape[0]
    kv_x = x if kv_x is None else kv_x
    q = apply_ptc_linear(p["wq"], x, lin, d_out=cfg.n_heads * cfg.head_dim,
                         name="wq")
    k = apply_ptc_linear(p["wk"], kv_x, lin,
                         d_out=cfg.n_kv_heads * cfg.head_dim, name="wk")
    v = apply_ptc_linear(p["wv"], kv_x, lin,
                         d_out=cfg.n_kv_heads * cfg.head_dim, name="wv")
    q = q.reshape(b, x.shape[1], cfg.n_heads, cfg.head_dim)
    k = k.reshape(b, kv_x.shape[1], cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(b, kv_x.shape[1], cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        q = rmsnorm(p["qn"], q)
        k = rmsnorm(p["kn"], k)
    if cfg.rope_frac > 0 and positions is not None:
        cos, sin = rotary_cache(positions, cfg.head_dim, cfg.rope_theta,
                                cfg.rope_frac)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
    return q, k, v


def _mask_bias(sq, sk, causal, window, q_offset=0, dtype=jnp.float32):
    qi = jnp.arange(sq)[:, None] + q_offset
    ki = jnp.arange(sk)[None, :]
    ok = jnp.ones((sq, sk), bool)
    if causal:
        ok = ok & (ki <= qi)
    if window is not None:
        ok = ok & (ki > qi - window)
    return jnp.where(ok, 0.0, NEG_INF).astype(dtype)


def _sdpa(q, k, v, cfg: AttnCfg, q_offset=0):
    """Materialized-scores attention (training / short prefill)."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    rep = h // k.shape[2]
    kr = jnp.repeat(k, rep, axis=2)
    vr = jnp.repeat(v, rep, axis=2)
    scale = hd ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(jnp.float32) * scale
    logits = softcap(logits, cfg.attn_softcap)
    logits = logits + _mask_bias(sq, sk, cfg.causal, cfg.window, q_offset)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, vr)


def _sdpa_chunked(q, k, v, cfg: AttnCfg, chunk: int):
    """Online-softmax attention over KV chunks: O(S·chunk) memory.

    The long-prefill path; mathematically identical to _sdpa."""
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    assert sk % chunk == 0, (sk, chunk)
    rep = h // k.shape[2]
    scale = hd ** -0.5
    kc = k.reshape(b, sk // chunk, chunk, k.shape[2], hd)
    vc = v.reshape(b, sk // chunk, chunk, v.shape[2], hd)
    qi = jnp.arange(sq)[:, None]

    def body(carry, ckv):
        acc, m, denom, ci = carry
        kb, vb = ckv
        kb = jnp.repeat(kb, rep, axis=2)
        vb = jnp.repeat(vb, rep, axis=2)
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kb).astype(jnp.float32) * scale
        logits = softcap(logits, cfg.attn_softcap)
        ki = ci * chunk + jnp.arange(chunk)[None, :]
        ok = jnp.ones((sq, chunk), bool)
        if cfg.causal:
            ok = ok & (ki <= qi)
        if cfg.window is not None:
            ok = ok & (ki > qi - cfg.window)
        logits = logits + jnp.where(ok, 0.0, NEG_INF)
        m_new = jnp.maximum(m, logits.max(-1))
        alpha = jnp.exp(m - m_new)
        pexp = jnp.exp(logits - m_new[..., None])
        denom = denom * alpha + pexp.sum(-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", pexp.astype(q.dtype), vb).astype(jnp.float32)
        return (acc, m_new, denom, ci + 1), None

    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    d0 = jnp.zeros((b, h, sq), jnp.float32)
    # checkpoint per KV chunk: backward recomputes each chunk's logits
    # instead of saving (B, H, S, S_k) — peak memory O(S·chunk)
    (acc, _, denom, _), _ = jax.lax.scan(
        jax.checkpoint(body), (acc0, m0, d0, jnp.asarray(0)),
        (jnp.swapaxes(kc, 0, 1), jnp.swapaxes(vc, 0, 1)))
    out = acc / denom[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def attention(p: Params, cfg: AttnCfg, lin: PTCLinearCfg, x, positions,
              kv_x=None, chunk: int | None = None):
    """Full attention layer: project → attend → output projection."""
    q, k, v = _project_qkv(p, cfg, lin, x, positions, kv_x)
    if chunk is not None and k.shape[1] > chunk:
        o = _sdpa_chunked(q, k, v, cfg, chunk)
    else:
        o = _sdpa(q, k, v, cfg)
    b, s = x.shape[0], x.shape[1]
    o = o.reshape(b, s, cfg.n_heads * cfg.head_dim)
    return apply_ptc_linear(p["wo"], o, lin, d_out=cfg.d_model, name="wo")


# -- decode (serve path) -----------------------------------------------------


def _grouped_decode(q, k, v, ok, cfg: AttnCfg, new=None):
    """One query row per head against K/V read by KV-head group.

    q: (B, 1, H, Dh); k/v: (B, S, Hkv, Dh), read in their own layout with
    no per-head repeat: q is taken as (B, Hkv, rep, Dh), so head h is
    member ``h % rep`` of group ``h // rep`` — ``jnp.repeat(k, rep,
    axis=2)``'s order.  ok: (B or 1, S) bool, the keys each row may
    attend.  ``new``: optional (k_new, v_new), each (B, 1, Hkv, Dh) in
    k's dtype, taken as one more key after column S that every row
    attends.  Returns (B, 1, H·Dh) in q's dtype."""
    b, _, h, dh = q.shape
    s, g = k.shape[1], k.shape[2]
    qg = q.reshape(b, g, h // g, dh)
    logits = jnp.einsum("bgrd,bsgd->bgrs", qg, k)
    if new is not None:
        col = jnp.einsum("bgrd,bgd->bgr", qg, new[0][:, 0])
        logits = jnp.concatenate([logits, col[..., None]], axis=-1)
        ok = jnp.pad(ok, ((0, 0), (0, 1)), constant_values=True)
    logits = logits.astype(jnp.float32) * (dh ** -0.5)
    logits = softcap(logits, cfg.attn_softcap)
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bgrs,bsgd->bgrd", w[..., :s], v,
                   preferred_element_type=jnp.float32)
    if new is not None:
        o = o + jnp.einsum("bgr,bgd->bgrd", w[..., s], new[1][:, 0],
                           preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(b, 1, h * dh)


def init_kv_cache(batch: int, max_len: int, cfg: AttnCfg, dtype=jnp.bfloat16):
    shape = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def decode_attention(p: Params, cfg: AttnCfg, lin: PTCLinearCfg, x, cache,
                     cache_len):
    """One-token decode against a populated KV cache.

    x: (B, 1, d); cache k/v: (B, S, Hkv, Dh); cache_len: scalar/ (B,) —
    number of valid cache entries.  Returns (out, updated_cache)."""
    b = x.shape[0]
    positions = jnp.full((b, 1), cache_len, jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, lin, x, positions)
    k = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), cache_len, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), cache_len, axis=1)
    ki = jnp.arange(k.shape[1])[None, :]
    ok = ki <= cache_len
    if cfg.window is not None:
        ok = ok & (ki > cache_len - cfg.window)
    o = _grouped_decode(q, k, v, ok, cfg)
    out = apply_ptc_linear(p["wo"], o, lin, d_out=cfg.d_model, name="wo")
    return out, {"k": k, "v": v}


def decode_attention_paged(p: Params, cfg: AttnCfg, lin: PTCLinearCfg, x,
                           k_view, v_view, lens):
    """One-token decode against page-assembled per-slot KV views with
    *per-sequence* cache lengths (the continuous-batching gateway path).

    x: (B, 1, d); k_view/v_view: (B, S_max, Hkv, Dh) contiguous views
    gathered from the page pool; lens: (B,) int32 valid lengths —
    heterogeneous across the batch, unlike :func:`decode_attention`'s
    shared scalar.  Slot b attends view rows ``< lens[b]`` (inside the
    window, if any) and its own new row, which sits at position
    ``lens[b]``.

    The views are read once, in place, in their own layout: GQA is read
    by KV-head group (:func:`_grouped_decode`), with no per-head copy of
    K or V, and nothing is spliced into them — the new row is scored as
    one more key column after the view's S_max.

    Returns ``(out, k_new, v_new)``: the caller persists the new
    (B, 1, Hkv, Dh) rows into the page pool (``kernels.paged_scatter``);
    the assembled views are step-scratch and never written back.
    """
    lens = lens.astype(jnp.int32)
    q, k_new, v_new = _project_qkv(p, cfg, lin, x, lens[:, None])
    ki = jnp.arange(k_view.shape[1])[None, :]
    ln = lens[:, None]
    ok = ki < ln
    if cfg.window is not None:
        ok = ok & (ki > ln - cfg.window)
    o = _grouped_decode(q, k_view, v_view, ok, cfg,
                        new=(k_new.astype(k_view.dtype),
                             v_new.astype(v_view.dtype)))
    out = apply_ptc_linear(p["wo"], o, lin, d_out=cfg.d_model, name="wo")
    return out, k_new, v_new


def decode_attention_paged_chunked(p: Params, cfg: AttnCfg,
                                   lin: PTCLinearCfg, x, k_view, v_view,
                                   lens):
    """C-token chunked prefill against page-assembled per-slot views.

    x: (B, C, d) — each slot's next C tokens (padding columns past the
    slot's ``n_valid`` are arbitrary: the causal mask plus the caller's
    length bookkeeping keep them out of every surviving value); lens:
    (B,) int32 cache lengths, so chunk column c sits at absolute
    position ``lens[b] + c``.  Attention runs through the Pallas
    online-softmax kernel (``kernels.prefill_attention``) over the view
    with the chunk's own K/V rows spliced in, one shape-derived KV block
    at a time.

    The splice deliberately avoids ``dynamic_update_slice`` — its start
    index CLAMPS, so a slot near the end of its reservation would slide
    the chunk backwards over valid history.  Instead each view row
    selects by absolute position: rows ``lens[b]+c`` take chunk column
    c, all others keep the pool value.

    Returns ``(out, k_new, v_new)`` with out (B, C, d) and k_new/v_new
    (B, C, Hkv, Dh) for the caller's multi-row page scatter.
    """
    b, c = x.shape[0], x.shape[1]
    lens = lens.astype(jnp.int32)
    positions = lens[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    q, k_new, v_new = _project_qkv(p, cfg, lin, x, positions)
    s = k_view.shape[1]
    rel = jnp.arange(s, dtype=jnp.int32)[None, :] - lens[:, None]  # (B, S)
    in_chunk = (rel >= 0) & (rel < c)
    sel = jnp.clip(rel, 0, c - 1)[:, :, None, None]

    def splice(view, new):
        g = jnp.take_along_axis(new.astype(view.dtype), sel, axis=1)
        return jnp.where(in_chunk[:, :, None, None], g, view)

    o = prefill_attention(lens, q, splice(k_view, k_new),
                          splice(v_view, v_new), window=cfg.window,
                          cap=cfg.attn_softcap)
    o = o.reshape(b, c, cfg.n_heads * cfg.head_dim)
    out = apply_ptc_linear(p["wo"], o, lin, d_out=cfg.d_model, name="wo")
    return out, k_new, v_new
