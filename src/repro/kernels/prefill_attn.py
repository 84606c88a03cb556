"""Pallas TPU kernel: chunked paged-prefill attention (serving gateway).

One grid step handles one slot × one KV block: a causal chunk of C
query tokens (the slot's next prompt tokens, already rope'd at absolute
positions ``lens[b] + c``) attends over the slot's page-assembled KV
view with an online-softmax accumulation (running max / denominator /
accumulator in VMEM scratch), so a long context is consumed block by
block and the full (C, S_max) score matrix never materializes beyond
one (C, blk) tile.

The caller splices the chunk's own freshly-projected K/V rows into the
view at ``lens[b]..lens[b]+C-1`` before the call, so in-chunk causal
attention (token c attending to tokens < c of the same chunk) falls out
of the ordinary position mask — the kernel needs no intra-chunk special
case.  Per-slot valid lengths ride in as a scalar-prefetch operand, the
same layout trick as ``paged_kv.py``.

Masking discipline for the online update: masked logits are forced to a
*finite* floor (NEG_INF) before the block max so an all-masked block
keeps the running max finite, and the exponentiated weights are zeroed
*by the mask* (not by the floor) so ``exp(NEG_INF - NEG_INF) = 1``
can never leak a masked key into the accumulator — that is what makes
a fully-out-of-window block contribute exactly +0.0 and keeps the
result bitwise independent of how many padding columns ride along.

The KV block is derived from the shapes (:func:`kv_block`): the
largest divisor of S_max whose per-step working set fits a fixed VMEM
budget, so a long view streams through in pieces instead of landing in
VMEM whole.  Both contractions accumulate in f32 on the MXU.

``interpret`` comes from ``kernels.ops``: interpret mode off-TPU
(kernel body executed by XLA:CPU), Mosaic on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["prefill_attention", "kv_block"]

NEG_INF = -2.0 ** 30   # finite floor: keeps max/exp arithmetic NaN-free

# Per-step working-set budget for the derived KV block: three quarters
# of the 16 MiB VMEM that Mosaic scopes to a kernel by default on v5e,
# leaving the rest for its own temporaries and relayouts.
VMEM_BUDGET = 12 * 2 ** 20


def _step_bytes(blk: int, c: int, h: int, hkv: int, hd: int,
                itemsize: int) -> int:
    """VMEM one grid step holds at KV block ``blk``: double-buffered
    q/out/k/v blocks, the GQA-expanded k/v (and v's f32 copy), f32
    logits/weights and the online-softmax scratch."""
    io = 2 * itemsize * (2 * c * h * hd + 2 * blk * hkv * hd)
    expanded = (2 * itemsize + 4) * blk * h * hd
    scores = 3 * 4 * h * c * blk
    scratch = 4 * h * c * (hd + 2)
    return io + expanded + scores + scratch


def kv_block(s: int, c: int, h: int, hkv: int, hd: int,
             itemsize: int) -> int:
    """Largest KV block that divides S, is a multiple of 8 (or all of
    S) and fits :data:`VMEM_BUDGET`; the smallest such block otherwise."""
    fits = [d for d in range(s, 0, -1)
            if s % d == 0 and (d % 8 == 0 or d == s)]
    return next((d for d in fits
                 if _step_bytes(d, c, h, hkv, hd, itemsize) <= VMEM_BUDGET),
                fits[-1])


def _prefill_kernel(lens_ref, q_ref, k_ref, v_ref, out_ref,
                    acc_ref, m_ref, denom_ref, *,
                    blk, rep, scale, cap, window):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        denom_ref[...] = jnp.zeros_like(denom_ref)

    q = q_ref[0]                                   # (C, H, Dh)
    kb = jnp.repeat(k_ref[0], rep, axis=1)         # (blk, H, Dh) GQA expand
    vb = jnp.repeat(v_ref[0], rep, axis=1)
    c = q.shape[0]
    logits = jnp.einsum("qhd,khd->hqk", q, kb,
                        preferred_element_type=jnp.float32) * scale
    if cap is not None:
        logits = cap * jnp.tanh(logits / cap)
    ln = lens_ref[b]
    qi = ln + jax.lax.broadcasted_iota(jnp.int32, (c, blk), 0)
    ki = j * blk + jax.lax.broadcasted_iota(jnp.int32, (c, blk), 1)
    ok = ki <= qi
    if window is not None:
        ok = ok & (ki > qi - window)
    logits = jnp.where(ok[None], logits, NEG_INF)
    m_new = jnp.maximum(m_ref[...], logits.max(-1))          # (H, C)
    alpha = jnp.exp(m_ref[...] - m_new)
    p = jnp.where(ok[None], jnp.exp(logits - m_new[..., None]), 0.0)
    denom_ref[...] = denom_ref[...] * alpha + p.sum(-1)
    acc_ref[...] = (acc_ref[...] * alpha[..., None]
                    + jnp.einsum("hqk,khd->hqd", p,
                                 vb.astype(jnp.float32),
                                 preferred_element_type=jnp.float32))
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        out = acc_ref[...] / denom_ref[...][..., None]
        out_ref[0] = jnp.swapaxes(out, 0, 1).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("blk", "window", "cap", "interpret"))
def prefill_attention(lens, q, k, v, *, blk: int | None = None,
                      window: int | None = None, cap: float | None = None,
                      interpret: bool = False):
    """Chunked-causal prefill attention over per-slot KV views.

    lens: (B,) int32 — tokens already in each slot's cache (the chunk's
    first query sits at absolute position ``lens[b]``).
    q: (B, C, H, Dh) rope'd queries for the C-token chunk.
    k, v: (B, S_max, Hkv, Dh) page-assembled views WITH the chunk's own
    rows already spliced in at ``lens[b]..lens[b]+C-1``.
    blk: KV block size (must divide S_max); None derives it from the
    shapes (:func:`kv_block`).  cap: attention logit soft-cap (gemma2);
    window: sliding window.  Returns (B, C, H, Dh) attended values in q's dtype.
    """
    b, c, h, hd = q.shape
    s, hkv = k.shape[1], k.shape[2]
    if blk is None:
        blk = kv_block(s, c, h, hkv, hd, k.dtype.itemsize)
    if s % blk:
        raise ValueError(f"kv view length {s} not divisible by block {blk}")
    kern = functools.partial(_prefill_kernel, blk=blk, rep=h // hkv,
                             scale=hd ** -0.5, cap=cap, window=window)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s // blk),
            in_specs=[
                pl.BlockSpec((1, c, h, hd), lambda bb, jj, t: (bb, 0, 0, 0)),
                pl.BlockSpec((1, blk, hkv, hd),
                             lambda bb, jj, t: (bb, jj, 0, 0)),
                pl.BlockSpec((1, blk, hkv, hd),
                             lambda bb, jj, t: (bb, jj, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, c, h, hd),
                                   lambda bb, jj, t: (bb, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((h, c, hd), jnp.float32),
                pltpu.VMEM((h, c), jnp.float32),
                pltpu.VMEM((h, c), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, c, h, hd), q.dtype),
        interpret=interpret,
    )(lens.astype(jnp.int32), q, k, v)
