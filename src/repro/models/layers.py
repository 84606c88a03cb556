"""Shared neural-net layers over the PTC substrate.

Every projection in every arch is a PTC linear — blockwise (U, Σ, V*)
factors with Σ the only first-order-trainable hardware leaf — unless
``mode="dense"`` selects the full-space electronic baseline the paper
compares against.  Embeddings, norms and routers are dense-trainable
(the paper likewise trains the non-photonic electronics normally).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..core.ptc import PTCParams, random_factorize
from ..core.subspace import ptc_linear, SubspaceMasks

__all__ = [
    "PTCLinearCfg", "init_ptc_linear", "apply_ptc_linear", "is_ptc_leaf",
    "ptc_execution", "ptc_scope", "ptc_scope_name",
    "init_rmsnorm", "rmsnorm", "layernorm_np", "init_layernorm", "layernorm",
    "rotary_cache", "apply_rotary", "softcap", "init_embedding", "embed",
    "trainable_mask", "maybe_constraint",
]


# -- layer-execution hook ----------------------------------------------------
#
# Hardware-in-the-loop serving substitutes a PTC linear's digital matmul
# with the *realized* transfer of a routed photonic chip
# (``runtime/hw_serve.py``).  The substitution point is here: while a
# hook is installed (``ptc_execution``), every *named* factored PTC
# linear offers its call to the hook first — `hook(name, p, x, cfg,
# d_out)` returns the layer output ``(..., m)`` computed elsewhere, or
# ``None`` to fall back to the digital path.  Names are qualified by the
# enclosing ``ptc_scope`` stack (the serve decode loop pushes
# ``p{period}.s{sublayer}.attn`` etc.), so one model forward yields a
# stable, enumerable layer naming that hw tenant placement keys on.
#
# The hook only ever fires on concrete (non-traced) inputs: under
# jit/scan/vmap the call sees tracers and silently stays digital, so a
# hooked serve loop must run unjitted + unrolled (launch/serve.py does).

_PTC_EXEC_HOOK: Callable | None = None
_PTC_SCOPE: list[str] = []


@contextlib.contextmanager
def ptc_execution(hook: Callable):
    """Install ``hook(name, p, x, cfg, d_out) -> y | None`` as the active
    PTC layer executor for the dynamic extent of the block.

    Never install this inside a function that jax traces (jit / scan /
    vmap bodies): dispatch is tracer-guarded, so under trace every PTC
    call silently stays digital and hardware-in-the-loop serving
    degrades to a simulation without an error.  repro-lint flags such
    installs statically (``python -m repro.analysis.lint --explain
    RPL302``); the legal pattern is installing around an unjitted,
    unrolled decode loop as ``launch/serve.py`` does."""
    global _PTC_EXEC_HOOK
    prev, _PTC_EXEC_HOOK = _PTC_EXEC_HOOK, hook
    try:
        yield
    finally:
        _PTC_EXEC_HOOK = prev


@contextlib.contextmanager
def ptc_scope(name: str):
    """Push a qualifier onto the PTC layer-name scope stack, and onto
    JAX's name stack, so every op traced inside carries it in its HLO
    ``op_name`` metadata (and a profile can attribute device time to
    ``s0.attn``, ``s0.mlp`` ...)."""
    _PTC_SCOPE.append(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        _PTC_SCOPE.pop()


def ptc_scope_name(leaf: str) -> str:
    """Qualified layer name for ``leaf`` under the current scope."""
    return ".".join((*_PTC_SCOPE, leaf))


def _hook_dispatch(p: Params, x: jax.Array, cfg: "PTCLinearCfg",
                   d_out: int | None, name: str | None):
    """Offer this call to the active execution hook; None = stay digital."""
    if (_PTC_EXEC_HOOK is None or name is None or cfg.mode == "dense"
            or "u" not in p or p["u"].ndim != 4):
        return None
    if isinstance(x, jax.core.Tracer):    # jit/vmap/scan context: digital
        return None
    return _PTC_EXEC_HOOK(ptc_scope_name(name), p, x, cfg, d_out)


def maybe_constraint(x: jax.Array, *spec) -> jax.Array:
    """Mesh-aware ``with_sharding_constraint``: entries are ``"dp"`` (all
    non-model axes), ``"model"``, or None.  Degrades to a no-op outside a
    mesh context (single-device tests) — used to steer the MoE G↔E
    reshard into an all-to-all instead of buffer replication."""
    from jax._src import mesh as _mesh_lib
    m = _mesh_lib.thread_resources.env.physical_mesh
    if m.empty:
        return x
    dp = tuple(a for a in m.axis_names if a != "model")
    resolved = []
    for e in spec:
        if e == "dp":
            resolved.append(dp if dp else None)
        elif e == "model":
            resolved.append("model" if "model" in m.axis_names else None)
        else:
            resolved.append(None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*resolved))

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class PTCLinearCfg:
    """Static policy for every PTC linear in a model."""

    k: int = 128               # block size (MXU-aligned default; 9 = paper)
    mode: str = "fused"        # fused | blocked | dense
    base_dtype: Any = jnp.bfloat16   # frozen U/V storage dtype
    sigma_dtype: Any = jnp.float32   # trainable Σ dtype


def init_ptc_linear(key: jax.Array, d_in: int, d_out: int,
                    cfg: PTCLinearCfg, bias: bool = False) -> Params:
    if cfg.mode == "dense":
        scale = float(np.sqrt(2.0 / (d_in + d_out)))
        p: Params = {"w": scale * jax.random.normal(
            key, (d_out, d_in), cfg.base_dtype)}
    else:
        f = random_factorize(key, d_out, d_in, cfg.k)
        p = {"u": f.u.astype(cfg.base_dtype),
             "s": f.s.astype(cfg.sigma_dtype),
             "v": f.v.astype(cfg.base_dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def is_ptc_leaf(path: tuple) -> bool:
    """True for the trainable Σ leaf of a PTC linear ('s' key)."""
    last = path[-1]
    name = getattr(last, "key", getattr(last, "name", None))
    return name == "s"


def apply_ptc_linear(p: Params, x: jax.Array, cfg: PTCLinearCfg,
                     masks: SubspaceMasks | None = None,
                     d_out: int | None = None,
                     name: str | None = None) -> jax.Array:
    """y = x @ Wᵀ (+b).  Handles k-padding on both sides.

    ``name`` identifies the layer to an installed :func:`ptc_execution`
    hook (hardware-in-the-loop serving); unnamed calls never leave the
    digital path.  A named call's ops also carry ``name`` as a scope of
    their HLO ``op_name``."""
    if name is None:
        return _apply_ptc_linear(p, x, cfg, masks, d_out, name)
    with jax.named_scope(name):
        return _apply_ptc_linear(p, x, cfg, masks, d_out, name)


def _apply_ptc_linear(p: Params, x: jax.Array, cfg: PTCLinearCfg,
                      masks: SubspaceMasks | None, d_out: int | None,
                      name: str | None) -> jax.Array:
    y = _hook_dispatch(p, x, cfg, d_out, name)
    if y is not None:
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        return y
    if cfg.mode == "dense":
        w = p["w"]
        y = x.astype(w.dtype) @ w.T
        if d_out is not None and d_out != w.shape[0]:
            y = y[..., :d_out]
    else:
        if masks is None and ("fb" in p or "col" in p):
            # masks injected into the param tree (lm.inject_masks) so that
            # scan/vmap slicing distributes them per layer/expert
            masks = SubspaceMasks(feedback=p.get("fb"), column=p.get("col"))
        params = PTCParams(u=p["u"], s=p["s"].astype(p["u"].dtype), v=p["v"])
        pp, qq = params.grid
        k = params.k
        n = x.shape[-1]
        if qq * k != n:
            x = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, qq * k - n)])
        lead = x.shape[:-1]
        y = ptc_linear(x.reshape(-1, qq * k).astype(params.u.dtype), params,
                       masks, mode=cfg.mode)
        y = y.reshape(lead + (pp * k,))
        if d_out is not None and d_out != pp * k:
            y = y[..., :d_out]
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def partition(params: Params, mask: Params) -> tuple[Params, Params]:
    """Split a pytree into (selected, rest) by a bool mask pytree; the
    non-selected side holds scalar-zero placeholders so both sides keep
    the full tree structure (cheap, DCE-able).

    Used to take gradients ONLY w.r.t. trainable leaves: differentiating
    through the frozen U/V bases would otherwise materialize ~2/3 of the
    param footprint as zero-gradient accumulators inside the scan
    backward (measured: 4.3 GB/device/layer on qwen3-moe)."""
    ph = lambda p: jnp.zeros((), p.dtype if hasattr(p, "dtype") else None)
    sel = jax.tree.map(lambda p, m: p if m else ph(p), params, mask)
    rest = jax.tree.map(lambda p, m: ph(p) if m else p, params, mask)
    return sel, rest


def combine(sel: Params, rest: Params, mask: Params) -> Params:
    return jax.tree.map(lambda a, b, m: a if m else b, sel, rest, mask)


def trainable_mask(params: Params) -> Params:
    """Bool pytree: True = optimizer updates this leaf.

    Trainable: Σ ('s'), biases, norms, embeddings, routers — everything
    EXCEPT the frozen U/V bases (and dense-baseline 'w' stays trainable:
    that is the paper's full-space reference)."""
    def f(path, leaf):
        name = None
        for e in reversed(path):
            name = getattr(e, "key", getattr(e, "name", None))
            if isinstance(name, str):
                break
        return name not in ("u", "v")
    return jax.tree_util.tree_map_with_path(f, params)


# -- norms -------------------------------------------------------------------


def init_rmsnorm(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32)}


def rmsnorm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    nrm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (nrm * p["g"]).astype(x.dtype)


def init_layernorm(d: int) -> Params:
    return {"g": jnp.ones((d,), jnp.float32), "b": jnp.zeros((d,), jnp.float32)}


def layernorm(p: Params, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"] + p["b"]).astype(x.dtype)


def layernorm_np(x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """OLMo's non-parametric LayerNorm (no affine params)."""
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)


# -- rotary ------------------------------------------------------------------


def rotary_cache(positions: jax.Array, head_dim: int,
                 theta: float = 10000.0, frac: float = 1.0
                 ) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables, (..., rot_dim/2).  ``frac`` < 1 = partial rotary
    (chatglm's 2d-RoPE rotates half the head dim)."""
    rot = int(head_dim * frac) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions[..., None].astype(jnp.float32) * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (..., S, H, D); cos/sin: (..., S, rot/2) broadcast over H."""
    rot2 = cos.shape[-1]
    xr, xp = x[..., : 2 * rot2], x[..., 2 * rot2:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    c, s = cos[..., None, :], sin[..., None, :]
    y1 = x1 * c - x2 * s
    y2 = x2 * c + x1 * s
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape)
    return jnp.concatenate([yr, xp], axis=-1).astype(x.dtype)


def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    """Gemma-2 logit soft-capping: cap·tanh(x/cap)."""
    if cap is None:
        return x
    return (cap * jnp.tanh(x.astype(jnp.float32) / cap)).astype(x.dtype)


# -- embedding ---------------------------------------------------------------


def init_embedding(key: jax.Array, vocab: int, d: int,
                   dtype=jnp.bfloat16) -> Params:
    return {"e": (jax.random.normal(key, (vocab, d), jnp.float32)
                  * (d ** -0.5)).astype(dtype)}


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["e"], tokens, axis=0)
