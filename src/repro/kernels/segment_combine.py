"""Pallas TPU kernel: sorted-segment combine (the scatter-combine hot loop).

The paper's scatter-combine channel pre-sorts edges by destination so the
per-superstep combine is a linear scan instead of hash routing. On TPU the
same preprocessing yields a *block-CSR segment reduction*:

  - destination rows are tiled into blocks of ``block_rows`` (the output
    tile, written lane-dense as one ``(D, block_rows)`` row per block),
  - the edge array (values + segment ids, already sorted by segment) is
    tiled into chunks of ``block_edges``, each laid out lane-dense as
    ``(rows, lanes)`` in edge order (``repro.kernels.layout``),
  - a host-side plan lists each row block's covering chunks as work
    items (scalar-prefetched, the standard block-sparse index-table
    pattern; see Grid below),
  - inside the kernel each lane row of a chunk (128 edges) is combined
    lane by lane into a ``(block_rows, lanes)`` VMEM accumulator: a
    masked select puts each edge's value on the accumulator row of its
    segment (``seg == row``) and the identity everywhere else. The ids
    are sorted, so a lane row's edges lie between its first and its last
    id, which it reads as scalars: a row with no edge in the block does
    nothing, and the others loop over the ``WINDOW``-row windows (one
    vector register each) between the two, so the work follows the rows
    the edges cover, not the block's size. A row whose ids span more
    than ``WIDE_WINDOWS`` windows (degree-1 destinations, mostly) takes
    one unrolled sweep of the whole block instead,
  - on its block's last item the accumulator is transposed and reduced
    down the sublanes into the block's ``(D, block_rows)`` output row.

Works for the ``sum``, ``min`` and ``max`` combiners in any dtype, with
no integer matmul and no narrowing reshape, so it lowers on every TPU
generation. Partials combine across chunks with the same combiner: for
``min``/``max`` and integer ``sum`` the result is exactly the
reference's, and a float ``sum`` differs from it in rounding order only.

Grid: one step per *work item*, a flat list of (row block, covering
chunk) pairs in row-block order with each block's chunks ascending (the
ragged-grid pattern of grouped-matmul kernels). Two scalar-prefetched
tables drive it: ``item_block[t]`` picks the output tile and
``item_chunk[t]`` the input chunk, so the revisits of one output tile are
consecutive — the canonical Pallas reduction — and the accumulator is
set to the identity on the first item of its block and written out on
its last. A block with no edges still gets one item (its row is the
identity); such an item, and the trailing padding items that repeat the
last real one, carry their chunk ``c`` as ``~c`` (= -(c + 1)): the index
map fetches ``c`` (the chunk already resident, or the next block's
first, so nothing is fetched twice) and the combine is skipped.
Neighbouring blocks share at most one covering chunk, so a call never
needs more than ``NB + EC`` items.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import combiners as cb
from repro.kernels.layout import chunk_tile

#: reduction per supported combiner (the kernel's whole menu)
_REDUCE = {"sum": jnp.sum, "min": jnp.min, "max": jnp.max}

#: rows of one window: the sublanes of one vector register
WINDOW_BITS = 3
WINDOW = 1 << WINDOW_BITS

#: most windows a lane row takes through the window loop; a row whose
#: ids span more (degree-1 destinations, mostly) takes one unrolled sweep
#: of the whole block instead. On a v5e compile a loop window is 9 ops
#: beside the loop's own scalar work, a sweep window 5, and a sweep
#: covers 16 windows at 128-row blocks, 32 at 256.
WIDE_WINDOWS = 4


def _kernel(ib_ref, ic_ref, seg_ref, vals_ref, o_ref, acc_ref, *, combiner,
            block_rows):
    t = pl.program_id(0)
    last = pl.num_programs(0) - 1
    i = ib_ref[t]
    dtype = np.dtype(o_ref.dtype)
    ident = dtype.type(combiner.ident_for(dtype))  # no dtype promotion
    n_d, n_r, n_l = vals_ref.shape

    @pl.when((t == 0) | (i != ib_ref[jnp.maximum(t - 1, 0)]))
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, ident)

    def accumulate(rows, at, rel, vals):
        """Combine a lane row's values ``vals`` (one per value column) into
        the accumulator rows ``at``, whose row numbers are ``rows``; ``rel``
        is each edge's row."""
        mine = rows == rel
        for d in range(n_d):
            picked = jnp.where(mine, vals[d], ident)
            acc_ref[d, at, :] = combiner.fn(acc_ref[d, at, :], picked)

    @pl.when(ic_ref[t] >= 0)
    def _compute():
        base = i * block_rows
        window_rows = jax.lax.broadcasted_iota(jnp.int32, (WINDOW, n_l), 0)
        all_rows = jax.lax.broadcasted_iota(jnp.int32, (block_rows, n_l), 0)
        for r in range(n_r):
            # ids ascend along the row: its first and last bound its rows
            first = seg_ref[r, 0] - base
            final = seg_ref[r, n_l - 1] - base
            w_lo = jnp.maximum(first, 0) >> WINDOW_BITS
            w_hi = jnp.minimum(final, block_rows - 1) >> WINDOW_BITS
            hit = (first < block_rows) & (final >= 0)
            wide = w_hi - w_lo >= WIDE_WINDOWS

            @pl.when(hit & jnp.logical_not(wide))
            def _narrow():
                rel = jnp.broadcast_to(seg_ref[r:r + 1, :] - base,
                                       (WINDOW, n_l))
                vals = [jnp.broadcast_to(vals_ref[d, r:r + 1, :],
                                         (WINDOW, n_l)) for d in range(n_d)]

                def window(w, carry):
                    off = pl.multiple_of(w * WINDOW, WINDOW)
                    accumulate(window_rows + off, pl.ds(off, WINDOW), rel,
                               vals)
                    return carry

                jax.lax.fori_loop(w_lo, w_hi + 1, window, 0)

            @pl.when(hit & wide)
            def _wide():
                accumulate(all_rows, slice(None), seg_ref[r:r + 1, :] - base,
                           [vals_ref[d, r:r + 1, :] for d in range(n_d)])

    @pl.when((t == last) | (i != ib_ref[jnp.minimum(t + 1, last)]))
    def _write():
        reduce = _REDUCE[combiner.name]
        for d in range(n_d):
            o_ref[d:d + 1, :] = reduce(acc_ref[d].T, axis=0, keepdims=True)


def segment_combine_pallas(
    vals,
    seg_ids,
    item_block,
    item_chunk,
    *,
    num_segments: int,
    combiner,
    block_rows: int = 128,
    block_edges: int = 512,
    interpret: bool = True,
):
    """Block-CSR segment combine over a flat work list.

    Args:
      vals: (E_pad, D) values, sorted by segment; padded entries must have
        seg_ids >= num_segments (any value).
      seg_ids: (E_pad,) int32 sorted segment ids.
      item_block: (T,) int32 row block of each work item, nondecreasing.
      item_chunk: (T,) int32 chunk of each work item, ascending within a
        block; ``~c`` for an item that fetches chunk ``c`` and combines
        nothing (an empty block's item, or trailing padding).
      num_segments: output rows (padded to a multiple of block_rows).
      combiner: ``sum``, ``min`` or ``max`` (name or Combiner).
      block_edges: chunk length (at most 128, or a multiple of 128).
    Returns:
      (num_segments, D) combined values (identity for empty segments).
    """
    combiner = cb.get(combiner)
    if combiner.name not in _REDUCE:
        raise ValueError(
            f"segment_combine kernel has no reduction for combiner "
            f"{combiner.name!r} (one of {tuple(_REDUCE)})")
    E, D = vals.shape
    assert E % block_edges == 0, (E, block_edges)
    assert num_segments % block_rows == 0, (num_segments, block_rows)
    nb = num_segments // block_rows
    ec = E // block_edges
    r, l = chunk_tile(block_edges)

    def chunk(t, ib_ref, ic_ref):
        c = ic_ref[t]
        return jnp.where(c < 0, ~c, c)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(item_block.shape[0],),
        in_specs=[
            pl.BlockSpec((pl.squeezed, r, l),
                         lambda *a: (chunk(*a), 0, 0)),
            pl.BlockSpec((D, pl.squeezed, r, l),
                         lambda *a: (0, chunk(*a), 0, 0)),
        ],
        out_specs=pl.BlockSpec((pl.squeezed, D, block_rows),
                               lambda t, ib, ic: (ib[t], 0, 0)),
        scratch_shapes=[pltpu.VMEM((D, block_rows, l), vals.dtype)],
    )
    kernel = functools.partial(_kernel, combiner=combiner,
                               block_rows=block_rows)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nb, D, block_rows), vals.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(
        jnp.asarray(item_block, jnp.int32),
        jnp.asarray(item_chunk, jnp.int32),
        jnp.asarray(seg_ids, jnp.int32).reshape(ec, r, l),
        vals.T.reshape(D, ec, r, l),
    )
    return out.transpose(0, 2, 1).reshape(num_segments, D)
