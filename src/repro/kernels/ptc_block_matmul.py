"""Pallas TPU kernel: blockwise PTC forward ``y_p = Σ_q U_pq(Σ_pq ⊙ (V*_pq x_q))``.

The paper's photonic dataflow — input mesh, attenuator column, output
mesh, electronic cross-PTC accumulation — maps onto the TPU as three
VMEM-resident ops per (p, q) block: two k×k MXU matmuls around a VPU
scale, accumulated over q into the output tile.

Tiling: grid = (T/T_TILE, P, Q), q innermost so output revisits are
consecutive (standard TPU accumulation pattern).  Per grid step the
working set is ``T_TILE·k (x) + 2·k² (U,V) + k (s) + T_TILE·k (acc)``
floats — at the production k=128, T_TILE=256 that is ~0.6 MB, well
inside the ~16 MB VMEM budget; k=128 also exactly fills the MXU's
128×128 systolic array (DESIGN §3: block size is the hardware-alignment
knob on TPU, not a noise-robustness compromise).

Layout rules Mosaic enforces on the blocks: the x/y column blocks
``(T_TILE, k)`` need k to be a multiple of 128 (:func:`accepts`), and
T_TILE a multiple of 8 or all of T (:func:`row_tile`).  Σ rides as
(P, Q, 1, k) so its ``(1, k)`` block spans whole minor axes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["ptc_block_matmul", "accepts", "row_tile"]


def accepts(k: int) -> bool:
    """Whether Mosaic lowers the kernel at block size ``k``: the column
    blocks of x and y must span whole 128-lane tiles."""
    return k % 128 == 0


def row_tile(t: int, cap: int = 256) -> int:
    """Rows per grid step: the largest multiple of 8 that divides t and
    is at most ``cap``; all t rows (one block) when there is none."""
    return max((d for d in range(8, min(t, cap) + 1, 8) if t % d == 0),
               default=t)


def _kernel(x_ref, u_ref, s_ref, v_ref, o_ref):
    q = pl.program_id(2)

    @pl.when(q == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    x = x_ref[...]                       # (T_TILE, k)
    v = v_ref[0, 0]                      # (k, k) = V*_pq
    u = u_ref[0, 0]                      # (k, k) = U_pq
    s = s_ref[0, 0]                      # (1, k)
    yv = jnp.dot(x, v.T, preferred_element_type=jnp.float32)   # V* x
    ys = yv * s                                                # Σ ⊙ ·
    yu = jnp.dot(ys, u.T, preferred_element_type=jnp.float32)  # U ·
    o_ref[...] += yu.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ptc_block_matmul(x: jax.Array, u: jax.Array, s: jax.Array, v: jax.Array,
                     *, interpret: bool = False) -> jax.Array:
    """x: (T, Q·k), u/v: (P, Q, k, k), s: (P, Q, k) → y: (T, P·k)."""
    t, n = x.shape
    p, q, k, _ = u.shape
    assert n == q * k, (n, q, k)
    t_tile = row_tile(t)
    grid = (t // t_tile, p, q)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((t_tile, k), lambda i, pp, qq: (i, qq)),
            pl.BlockSpec((1, 1, k, k), lambda i, pp, qq: (pp, qq, 0, 0)),
            pl.BlockSpec((1, 1, 1, k), lambda i, pp, qq: (pp, qq, 0, 0)),
            pl.BlockSpec((1, 1, k, k), lambda i, pp, qq: (pp, qq, 0, 0)),
        ],
        out_specs=pl.BlockSpec((t_tile, k), lambda i, pp, qq: (i, pp)),
        out_shape=jax.ShapeDtypeStruct((t, p * k), x.dtype),
        interpret=interpret,
    )(x, u, s.reshape(p, q, 1, k), v)
