"""Dispatch layer: jit'd public ops over the Pallas kernels.

Whether a kernel runs through the interpreter is decided here and
nowhere else (:func:`default_interpret`): on a TPU every call compiles
to Mosaic; on any other backend the same kernel bodies run in interpret
mode, so the exact kernel code paths are validated on CPU hosts.  The
kernel modules themselves default to ``interpret=False``.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

from ..core.unitary import MeshSpec
from .ptc_block_matmul import (ptc_block_matmul as _ptc_block_matmul,
                               row_tile)
from .mesh_apply import mesh_apply_butterfly as _mesh_apply_butterfly
from .feedback_matmul import feedback_matmul as _feedback_matmul
from .sigma_grad import sigma_grad as _sigma_grad
from .paged_kv import (paged_gather as _paged_gather,
                       paged_scatter as _paged_scatter,
                       paged_scatter_rows as _paged_scatter_rows)
from .prefill_attn import prefill_attention as _prefill_attention

__all__ = ["default_interpret", "ptc_block_matmul", "mesh_apply",
           "feedback_matmul", "sigma_grad", "paged_gather", "paged_scatter",
           "paged_scatter_rows", "prefill_attention"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def ptc_block_matmul(x, u, s, v):
    """Blocked PTC forward (paper dataflow) via the Pallas kernel."""
    return _ptc_block_matmul(x, u, s, v, interpret=default_interpret())


def _coeff_tables(spec: MeshSpec, phases, dtype):
    """Per-layer wire coefficient tables (cheap, O(T) cos/sin)."""
    slot = jnp.asarray(spec.layer_slot)          # (L, k)
    sign = jnp.asarray(spec.layer_sign, dtype)   # (L, k)
    live = slot >= 0
    ph = jnp.where(live, jnp.take(phases, jnp.maximum(slot, 0)), 0.0)
    c = jnp.where(live, jnp.cos(ph), 1.0).astype(dtype)
    s = (jnp.where(live, jnp.sin(ph), 0.0) * sign).astype(dtype)
    return c, s, sign


def mesh_apply(spec: MeshSpec, phases, x, d=None):
    """U(Φ, D) @ x via the butterfly kernel.  x: (B, k); phases: (T,)."""
    c, s, sign = _coeff_tables(spec, phases, x.dtype)
    if d is None:
        d = jnp.ones((spec.k,), x.dtype)
    return _mesh_apply_butterfly(c, s, sign, d.astype(x.dtype), x,
                                 b_tile=row_tile(x.shape[0]),
                                 interpret=default_interpret())


def feedback_matmul(dy, u, s, v, mask):
    """Block-masked feedback pass via the predicated Pallas kernel."""
    return _feedback_matmul(dy, u, s, v, mask,
                            t_tile=row_tile(dy.shape[0]),
                            interpret=default_interpret())


def sigma_grad(dy, x, u, v):
    """Fused in-situ Σ-gradient (paper Eq. 5) via the Pallas kernel."""
    return _sigma_grad(dy, x, u, v, t_tile=row_tile(dy.shape[0]),
                       interpret=default_interpret())


def paged_gather(table, pages):
    """Paged-KV page assembly (serving gateway) via the Pallas kernel."""
    return _paged_gather(table, pages, interpret=default_interpret())


def paged_scatter(idx, new, pages):
    """Paged-KV token insertion (serving gateway) via the Pallas kernel."""
    return _paged_scatter(idx, new, pages, interpret=default_interpret())


def paged_scatter_rows(idx, rows, pages):
    """Multi-token paged-KV insertion (chunked prefill) in one call."""
    return _paged_scatter_rows(idx, rows, pages,
                               interpret=default_interpret())


def prefill_attention(lens, q, k, v, *, window=None, cap=None):
    """Chunked paged-prefill attention (serving gateway) via Pallas; the
    KV block is derived from the shapes (``prefill_attn.kv_block``)."""
    return _prefill_attention(lens, q, k, v, window=window, cap=cap,
                              interpret=default_interpret())
