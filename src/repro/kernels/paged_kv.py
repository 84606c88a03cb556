"""Pallas TPU kernels: paged KV-cache page assembly (gather/scatter).

The serving gateway (``repro.serving``) stores every in-flight request's
KV history in fixed-size pages of one shared pool; per-request *page
tables* map logical token blocks to physical pages.  Decode needs two
data movements per step:

* **gather** — assemble each request slot's pages into a contiguous
  (S_max, d) view the attention kernel can consume.  On TPU the page
  table rides in as a scalar-prefetch operand
  (``PrefetchScalarGridSpec``), so the index map can address the page
  dimension *before* the kernel body runs and each (slot, page) grid
  step is ONE VMEM-resident block copy — the standard paged-attention
  DMA idiom.  No compute, pure layout: the copy is exact, so the
  assembled view is bit-identical to the pool contents.
* **scatter** — write each slot's freshly projected k/v row into its
  current (page, offset) write position, in place: the pool stays in
  HBM (``memory_space=pl.ANY``), aliased into the output
  (``input_output_aliases``) and donated by the jit around the call,
  so XLA puts no copy of the pool before it, and each row lands by one
  DMA.  A pool of real size is hundreds of MB, far beyond VMEM, and a
  one-row store at a dynamic sublane offset does not lower; a DMA that
  slices only the page and offset axes does both.

Both kernels take ``interpret`` from ``kernels.ops``, which decides it
once: interpret mode off-TPU (the kernel bodies run on XLA:CPU),
Mosaic on a TPU.  Pool/table shapes are static — only the table
*contents* change per step — so both calls jit cleanly.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_gather", "paged_scatter", "paged_scatter_rows"]


def _gather_kernel(tbl_ref, pages_ref, out_ref):
    # grid (slot b, page j): the in_spec already DMA'd page tbl[b, j]
    # into pages_ref; emit it as the j-th block of slot b's view.
    out_ref[0, 0] = pages_ref[0]


@functools.partial(jax.jit, static_argnames=("lead", "interpret"))
def paged_gather(table: jax.Array, pages: jax.Array, *,
                 lead: tuple[int, ...] | None = None,
                 interpret: bool = False) -> jax.Array:
    """Assemble per-slot contiguous KV views from a paged pool.

    table: (B, J) int32 physical page ids (unallocated entries must
    hold a valid id — 0 by convention; attention masks them by length).
    pages: (n_pages, page_size, *row).  Returns (B, J·page_size, *row),
    or with ``lead`` (whose product is B, e.g. ``(periods, slots)``)
    ``(*lead, J·page_size, *row)``: the split happens inside this
    program, where it only relabels major axes, so the view is never
    copied into a buffer of its own.  The table rides flat in SMEM (a
    2-D SMEM array pads its rows to 128 words).
    """
    b, j = table.shape
    ps, row = pages.shape[1], pages.shape[2:]
    zeros = (0,) * len(row)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, j),
            in_specs=[pl.BlockSpec((1, ps) + row,
                                   lambda bb, jj, t: (t[bb * j + jj], 0)
                                   + zeros)],
            out_specs=pl.BlockSpec((1, 1, ps) + row,
                                   lambda bb, jj, t: (bb, jj, 0) + zeros)),
        out_shape=jax.ShapeDtypeStruct((b, j, ps) + row, pages.dtype),
        interpret=interpret,
    )(table.reshape(-1), pages)
    return out.reshape((lead or (b,)) + (j * ps,) + row)


def _scatter_kernel(idx_ref, rows_hbm, pages_hbm, out_hbm, sem):
    del pages_hbm                     # aliased into out_hbm
    r = pl.program_id(0)
    # one row, HBM → HBM, sliced on the untiled leading axes only; it is
    # awaited before the next grid step, so rows that share a target
    # (idle slots on the scratch page) resolve last-wins
    copy = pltpu.make_async_copy(
        rows_hbm.at[pl.ds(r, 1)],
        out_hbm.at[idx_ref[2 * r], pl.ds(idx_ref[2 * r + 1], 1)],
        sem)
    copy.start()
    copy.wait()


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnames=("pages",))
def paged_scatter(idx: jax.Array, new: jax.Array, pages: jax.Array, *,
                  interpret: bool = False) -> jax.Array:
    """Write one new KV row per slot into its page-table position.

    idx: (B, 2) int32 — per slot ``(page_id, offset)`` write position
    (idle slots must point somewhere harmless, e.g. a scratch page).
    new: (B, *row); pages: (n_pages, page_size, *row), updated in place
    via output aliasing.  Returns the updated pool.  ``pages`` is
    donated, so the kernel writes into its buffer with no copy before
    it: the pool passed in is consumed, and the caller keeps only the
    result.  On a TPU ``row`` needs two or more axes (e.g.
    ``(Hkv, Dh)``): the DMAs slice the page and offset axes, and a
    one-row slice of a tiled (minor two) axis does not lower.
    """
    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(new.shape[0],),
            in_specs=[any_spec, any_spec],
            out_specs=any_spec,
            scratch_shapes=[pltpu.SemaphoreType.DMA(())]),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        input_output_aliases={2: 0},
        interpret=interpret,
    )(idx.reshape(-1), new.astype(pages.dtype), pages)


def paged_scatter_rows(idx: jax.Array, rows: jax.Array, pages: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """Multi-token scatter: R independent row writes in ONE aliased call.

    The chunked-prefill path writes C new KV entries per slot per step;
    the host splits each chunk against the slot's page table wherever it
    crosses a page boundary (``PagedKVPool.write_span``) and hands the
    flattened (R, 2) ``(page_id, offset)`` list here.  The scatter
    kernel is already row-count generic — the grid runs one program per
    row, sequentially, so duplicate targets (e.g. every invalid row
    parked on the scratch page) resolve deterministically last-wins —
    and the pool is updated in place through the same
    ``input_output_aliases`` wiring as the one-row path, and ``pages``
    is consumed in the same way.

    idx: (R, 2) int32; rows: (R, *row); pages: (n_pages, page_size, *row).
    """
    return paged_scatter(idx, rows, pages, interpret=interpret)
