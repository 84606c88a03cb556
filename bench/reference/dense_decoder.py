"""Plain reference of a dense decoder-only LM with PTC-factored linears.

Straightforward ``jax.numpy`` in float32 at HIGHEST matmul precision,
with no kernel, cache or batching of the program's.  It imports nothing
of the program: it reads the benchmark's own weights (``bench/weights``)
by the key names of the parameter tree and the sizes of the
configuration file.

The model: token embedding x sqrt(d); per layer a pre-norm attention
block (q, k, v, o linears; optional RMS qk-norm; rotary embedding on
interleaved pairs; causal softmax over grouped KV heads) and a pre-norm
gated MLP (silu(gate) * up, then down); a final norm and the tied
unembedding.  Every linear is stored as k x k blocks W_pq =
U_pq diag(s_pq) V_pq and is composed here in float32.

``precision="fp8"`` is the control: the model computed one step below
the bfloat16 the configuration states.  Every tensor the program keeps
in bfloat16 (weights, the residual stream, norm outputs, q, k, v,
attention weights and outputs, MLP activations, the final hidden state)
is rounded to float8 e4m3 with one scale per tensor; arithmetic inside
an op stays float32, as the program's accumulations do.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["dims", "final_hidden", "logits_at", "token_gaps"]

HIGHEST = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0


def dims(cfg: dict) -> tuple:
    """(d, heads, kv_heads, head_dim, ff, vocab, layers, norm, qk_norm,
    theta, eps): the static jit argument of the functions below."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    norm = cfg["assumed"]["norm"]
    eps = cfg.get("rms_norm_eps") if norm == "rmsnorm" else \
        cfg.get("layer_norm_eps")
    return (d, h, cfg["num_key_value_heads"], cfg.get("head_dim") or d // h,
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"], norm, bool(cfg["assumed"]["qk_norm"]),
            float(cfg["rope_theta"]), float(eps))


def _round_fp8(a):
    scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / E4M3_MAX
    return (a / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _store(a, precision):
    """``a`` as the model keeps it between ops: float32 for the
    reference, rounded to fp8 for the control."""
    return _round_fp8(a) if precision == "fp8" else a


def _matmul(x, w_t, precision):
    return _store(jnp.matmul(_store(x, precision), _store(w_t, precision),
                             precision=HIGHEST), precision)


def _weight(p, m, n):
    """(m, n) float32 weight from the (P, Q, k, k) factors."""
    u = p["u"].astype(jnp.float32)
    v = p["v"].astype(jnp.float32)
    s = p["s"].astype(jnp.float32)
    blocks = jnp.einsum("pqik,pqkj->pqij", u * s[..., None, :], v,
                        precision=HIGHEST)
    pp, qq, k, _ = blocks.shape
    return blocks.transpose(0, 2, 1, 3).reshape(pp * k, qq * k)[:m, :n]


def _linear(p, x, m, n, precision):
    return _matmul(x, _weight(p, m, n).T, precision)


def _norm(kind, p, x, eps):
    if kind == "rmsnorm":
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
        return y * p["g"].astype(jnp.float32)
    if kind == "layernorm_nonparam":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps)
    raise ValueError(f"unknown norm {kind!r}")


def _rotary(x, theta):
    """x: (B, S, H, D); rotate interleaved pairs (x[0::2], x[1::2]) by
    position * theta^(-2i/D)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv       # (S, D/2)
    c, sn = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * sn, x2 * c + x1 * sn], -1
                     ).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("dm", "precision"))
def _layer(x, lp, dm: tuple, precision: str):
    d, h, hkv, dh, ff, _, _, norm, qk_norm, theta, eps = dm
    b, s, _ = x.shape
    a = lp["attn"]

    def store(t):
        return _store(t, precision)

    y = store(_norm(norm, lp["ln1"], x, eps))
    q = _linear(a["wq"], y, h * dh, d, precision).reshape(b, s, h, dh)
    k = _linear(a["wk"], y, hkv * dh, d, precision).reshape(b, s, hkv, dh)
    v = _linear(a["wv"], y, hkv * dh, d, precision).reshape(b, s, hkv, dh)
    if qk_norm:
        q = _norm("rmsnorm", a["qn"], q, eps)
        k = _norm("rmsnorm", a["kn"], k, eps)
    q, k = store(_rotary(q, theta)), store(_rotary(k, theta))
    group = h // hkv
    q = q.reshape(b, s, hkv, group, dh)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k,
                        precision=HIGHEST) * dh ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = store(jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1))
    o = store(jnp.einsum("bhgqk,bkhd->bqhgd", w, v, precision=HIGHEST))
    x = store(x + _linear(a["wo"], o.reshape(b, s, h * dh), d, h * dh,
                          precision))
    mp = lp["mlp"]
    y = store(_norm(norm, lp["ln2"], x, eps))
    g = _linear(mp["gate"], y, ff, d, precision)
    u = _linear(mp["up"], y, ff, d, precision)
    return store(x + _linear(mp["down"], store(jax.nn.silu(g) * u), d, ff,
                             precision))


@functools.partial(jax.jit, static_argnames=("dm", "precision"))
def _embed(table, tokens, dm: tuple, precision: str):
    return _store(table[tokens].astype(jnp.float32) * dm[0] ** 0.5,
                  precision)


@functools.partial(jax.jit, static_argnames=("dm", "precision"))
def _final_norm(params_final, x, dm: tuple, precision: str):
    return _store(_norm(dm[7], params_final, x, dm[10]), precision)


def final_hidden(params, cfg: dict, tokens, precision: str = "f32"):
    """(B, S, d) float32 final-normed hidden states of a causal forward
    over ``tokens`` (B, S), one layer at a time."""
    dm = dims(cfg)
    x = _embed(params["embed"]["e"], tokens, dm, precision)
    stack = params["pos0"]
    for i in range(dm[6]):
        x = _layer(x, jax.tree.map(lambda a: a[i], stack), dm, precision)
    return _final_norm(params["final_norm"], x, dm, precision)


@functools.partial(jax.jit, static_argnames=("precision",))
def logits_at(table, hidden, pos, precision: str = "f32"):
    """(O, V) float32 logits of ``hidden`` (S, d) at positions ``pos``."""
    rows = hidden[pos]
    return _matmul(rows, table.astype(jnp.float32).T, precision)


@jax.jit
def _gap(ref, served):
    """Gap by which each served token's reference logit lies below the
    reference's best, in units of that position's logit spread."""
    pick = jnp.take_along_axis(ref, served[:, None], -1)[:, 0]
    return (ref.max(-1) - pick) / ref.std(-1)


def token_gaps(params, cfg: dict, tokens, pos, served,
               control: bool = False):
    """Per-token gaps, (B, O) numpy-able.  ``tokens`` (B, S): prompt plus
    served tokens but the last, right-padded; ``pos`` (B, O): the
    positions whose logits chose ``served`` (B, O).  With ``control``,
    the token scored at each position is the one the fp8 forward puts
    first, in place of the served one."""
    table = params["embed"]["e"]
    hid = final_hidden(params, cfg, tokens)
    hid8 = final_hidden(params, cfg, tokens, "fp8") if control else None
    out = []
    for i in range(tokens.shape[0]):
        ref = logits_at(table, hid[i], pos[i])
        pick = served[i]
        if control:
            pick = jnp.argmax(logits_at(table, hid8[i], pos[i], "fp8"), -1)
        out.append(_gap(ref, pick.astype(jnp.int32)))
    return jnp.stack(out)
