"""Jit-ready wrappers around the Pallas kernels, plan building, and the
kernel-path configuration surface.

``segment_combine`` is the public entry point used by the channels: it
dispatches to the Pallas kernel or to the pure-jnp reference depending on
``use_kernel``. The kernel path expects sorted segment ids (the
scatter-combine channel guarantees this by construction — that is the
paper's preprocessing insight). ``bucket_ranks`` is the analogous entry
point for the routing data plane (stable counting-sort ranks).

Configuration — resolved by :func:`resolve_use_kernel`, most specific
wins:

  1. an explicit ``use_kernel=`` argument at a call site;
  2. the :func:`use_kernel_scope` context (how ``Engine(use_kernel=...)``
     threads the knob through a compile);
  3. the ``REPRO_USE_KERNEL`` environment variable (``1/true/yes/on``);
  4. the backend default: **on** for TPU (the kernels are lowered for
     the chip there, never interpreted), off elsewhere (the
     interpret-mode kernel is a correctness vehicle on CPU, not a fast
     path).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import knobs
from repro.core import combiners as cb
from repro.kernels import bucket_route as kbucket
from repro.kernels import ref as kref
from repro.kernels import segment_combine as kseg
from repro.kernels.layout import chunk_tile
from repro.pregel import tracing

#: the kernel-vs-reference knob (explicit > use_kernel_scope >
#: REPRO_USE_KERNEL > backend default) — see repro.configs.knobs
USE_KERNEL = knobs.Knob(
    "use_kernel", env="REPRO_USE_KERNEL",
    default=lambda: jax.default_backend() == "tpu",
    parse=knobs.parse_bool, coerce=bool)


def resolve_use_kernel(use_kernel: Optional[bool] = None) -> bool:
    """The kernel-vs-reference decision for a call site (see module doc)."""
    return USE_KERNEL.resolve(use_kernel)


def use_kernel_scope(use_kernel: Optional[bool]):
    """Pin the kernel decision for every channel call under the scope
    (trace-time: wrap the compile, not the execution)."""
    return USE_KERNEL.scope(use_kernel)


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Pallas interpret mode: real lowering on TPU, interpreter elsewhere."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _next_pow2(x: int) -> int:
    return 1 if x <= 1 else 1 << (x - 1).bit_length()


# ---------------------------------------------------------------------------
# block-plan autotune (host-side, consumed by graph.pgraph.ScatterPlan)
# ---------------------------------------------------------------------------


#: work items one kernel call can take: its two scalar-prefetched tables,
#: ``T`` int32 each, live in the core's scalar memory (1 MiB on a v5e)
#: beside the kernel's own scalars; the chip's compiler refuses 2**17
MAX_ITEMS = 96 * 1024


def autotune_block_sizes(u_cap: int, e_cap: int) -> Tuple[int, int]:
    """Choose (block_rows, block_edges) for a sorted-segment combine from
    the edge distribution of a plan.

    Heuristic: size the output tile to the segment count (small graphs
    should not pad 8x past their rows), then size the edge chunk so one
    chunk covers roughly the edges of one row block (``avg_deg *
    block_rows``) — each row block then visits O(1) chunks, which is what
    keeps the revisited-output reduction grid shallow. Where the work
    list's bound ``NB + EC`` would pass :data:`MAX_ITEMS`, the row block
    doubles until it fits.
    """
    block_rows = min(128, max(8, _next_pow2(u_cap)))
    avg_deg = e_cap / max(u_cap, 1)
    while True:
        block_edges = min(2048, max(128, _next_pow2(int(avg_deg
                                                        * block_rows))))
        items = -(-u_cap // block_rows) + -(-e_cap // block_edges)
        if items <= MAX_ITEMS or block_rows >= u_cap:
            return block_rows, block_edges
        block_rows *= 2


def chunk_bounds(seg_ids, num_row_blocks, block_rows, block_edges, xp=np):
    """First covering chunk and covering-chunk count of each row block
    for sorted seg_ids (numpy on the host, ``xp=jnp`` on the device)."""
    bounds = xp.searchsorted(
        seg_ids, xp.arange(num_row_blocks + 1) * block_rows, side="left")
    lo, hi = bounds[:-1], bounds[1:]
    start = lo // block_edges
    count = xp.where(hi > lo, -(-hi // block_edges) - start, 0)
    return start, count


def build_work_list(start, count, num_chunks, n_items=None, xp=np):
    """The kernel's flat work list from :func:`chunk_bounds`:
    (item_block, item_chunk) int32 of length ``n_items``. Items run in
    row-block order, each block's chunks ascending, and a block with no
    covering chunk gets one item (it still sets its tile to the
    identity): ``sum(max(count, 1))`` items, the default length (numpy
    only), below ``NB + EC`` since neighbouring blocks share at most one
    chunk. An item that combines nothing (an empty block's, or trailing
    padding, which repeats the last real item) carries its chunk ``c`` as
    ``~c``. Built in numpy for a plan, with ``xp=jnp`` on the device."""
    if n_items is None:
        n_items = int(np.maximum(count, 1).sum())
    per = xp.maximum(count, 1)
    ends = xp.cumsum(per)
    t = xp.arange(n_items)
    blk = xp.minimum(xp.searchsorted(ends, t, side="right"), len(per) - 1)
    j = xp.minimum(t - (ends[blk] - per[blk]), per[blk] - 1)
    chunk = xp.clip(start[blk] + j, 0, num_chunks - 1)
    real = (j < count[blk]) & (t < ends[-1])
    return (blk.astype(xp.int32),
            xp.where(real, chunk, ~chunk).astype(xp.int32))


def _kernel_view(seg_ids_np, num_segments, block_rows, block_edges):
    """The sorted ids as :func:`segment_combine` hands them to the kernel:
    entries outside ``[0, num_segments)`` map to the padded row bound,
    and the edge axis is padded to a ``block_edges`` multiple. Returns
    (ids, padded row count)."""
    seg = np.asarray(seg_ids_np)
    n_pad = _round_up(max(num_segments, 1), block_rows)
    e_pad = _round_up(max(len(seg), 1), block_edges)
    seg = np.where((seg < 0) | (seg >= num_segments), n_pad, seg)
    seg = np.concatenate([seg, np.full(e_pad - len(seg), n_pad, seg.dtype)])
    return seg, n_pad


def plan_chunks(seg_ids_np, num_segments, block_rows, block_edges):
    """:func:`chunk_bounds` on the host against the *kernel's* padded view
    of the inputs, as :func:`segment_combine` builds it, so
    :func:`build_work_list` of the result can be passed as its
    ``work_list``. Returns (start, count, num_chunks)."""
    seg, n_pad = _kernel_view(seg_ids_np, num_segments, block_rows,
                              block_edges)
    start, count = chunk_bounds(seg, n_pad // block_rows, block_rows,
                                block_edges)
    return start, count, len(seg) // block_edges


def window_steps(seg_ids_np, num_segments, block_rows, block_edges,
                 item_block, item_chunk):
    """What one kernel call's body does over this work list (host count,
    numpy): (windows, wide_rows) — the ``WINDOW``-row windows its narrow
    lane rows take, and the lane rows that take the block-wide sweep
    instead (``repro.kernels.segment_combine``). A lane row spans the
    windows from its first id to its last, clipped to the item's block;
    one whose ids all lie outside the block counts in neither."""
    seg, _ = _kernel_view(seg_ids_np, num_segments, block_rows, block_edges)
    r, l = chunk_tile(block_edges)
    rows = seg.reshape(-1, r, l)
    item_block, item_chunk = np.asarray(item_block), np.asarray(item_chunk)
    real = item_chunk >= 0
    base = (item_block[real] * block_rows)[:, None]  # (items, 1)
    first = rows[item_chunk[real], :, 0] - base      # (items, R)
    final = rows[item_chunk[real], :, -1] - base
    hit = (first < block_rows) & (final >= 0)
    span = (np.minimum(final, block_rows - 1) // kseg.WINDOW
            - np.maximum(first, 0) // kseg.WINDOW + 1)
    wide = hit & (span > kseg.WIDE_WINDOWS)
    return int(np.where(hit & ~wide, span, 0).sum()), int(wide.sum())


# ---------------------------------------------------------------------------
# segment combine (scatter-combine hot loop)
# ---------------------------------------------------------------------------


@tracing.scope(tracing.COMBINE)
def segment_combine(
    vals,
    seg_ids,
    num_segments: int,
    combiner,
    *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    block_rows: int = 128,
    block_edges: int = 512,
    work_list=None,
    assume_sorted: bool = False,
):
    """Segment reduction: out[s] = combine(vals[e] for seg_ids[e] == s).

    Entries with seg_ids >= num_segments are dropped. The kernel path
    requires sorted seg_ids (assume_sorted or it sorts internally) and
    runs over ``work_list``, a plan's (item_block, item_chunk)
    (:func:`build_work_list` of :func:`plan_chunks`), or one built on the
    device at the bound NB + EC. Both paths run under the ``combine``
    device scope.
    """
    combiner = cb.get(combiner)
    if not resolve_use_kernel(use_kernel):
        return kref.segment_combine_ref(vals, seg_ids, num_segments, combiner)

    vals = jnp.asarray(vals)
    squeeze = vals.ndim == 1
    if squeeze:
        vals = vals[:, None]
    seg_ids = jnp.asarray(seg_ids, jnp.int32)
    if not assume_sorted:
        order = jnp.argsort(seg_ids)
        seg_ids = seg_ids[order]
        vals = vals[order]

    e, d = vals.shape
    n_pad = _round_up(max(num_segments, 1), block_rows)
    e_pad = _round_up(max(e, 1), block_edges)
    ident = combiner.ident_for(vals.dtype)
    if e_pad != e:
        vals = jnp.concatenate(
            [vals, jnp.full((e_pad - e, d), ident, vals.dtype)], 0
        )
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((e_pad - e,), n_pad, jnp.int32)], 0
        )
    # Out-of-range (padded/dropped) entries: push past the last row block.
    seg_ids = jnp.where(
        (seg_ids < 0) | (seg_ids >= num_segments), n_pad, seg_ids
    )

    if work_list is None:
        nb, ec = n_pad // block_rows, e_pad // block_edges
        start, count = chunk_bounds(seg_ids, nb, block_rows, block_edges,
                                    xp=jnp)
        work_list = build_work_list(start, count, ec, nb + ec, xp=jnp)
    item_block, item_chunk = work_list

    out = kseg.segment_combine_pallas(
        vals,
        seg_ids,
        item_block,
        item_chunk,
        num_segments=n_pad,
        combiner=combiner,
        block_rows=block_rows,
        block_edges=block_edges,
        interpret=resolve_interpret(interpret),
    )[:num_segments]
    return out[:, 0] if squeeze else out


# ---------------------------------------------------------------------------
# bucket ranks (routing data plane)
# ---------------------------------------------------------------------------


def bucket_ranks(
    keys,
    num_buckets: int,
    *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    block_msgs: int = 1024,
):
    """Stable arrival rank of each message within its bucket, plus the
    per-bucket occupancy — the permutation core of the one-pass routed
    exchange (see ``repro.core.routing``).

    Args:
      keys: (M,) int32 bucket per message in ``[0, num_buckets]`` where
        ``num_buckets`` is the invalid sentinel.
      num_buckets: static bucket count (the worker count W).
    Returns:
      (rank (M,) int32, counts (num_buckets,) int32).
    """
    keys = jnp.asarray(keys, jnp.int32)
    if not resolve_use_kernel(use_kernel):
        return kref.bucket_ranks_ref(keys, num_buckets)
    m = keys.shape[0]
    m_pad = _round_up(max(m, 1), block_msgs)
    if m_pad != m:
        keys = jnp.concatenate(
            [keys, jnp.full((m_pad - m,), num_buckets, jnp.int32)]
        )
    rank, counts = kbucket.bucket_ranks_pallas(
        keys,
        num_buckets=num_buckets,
        block_msgs=block_msgs,
        interpret=resolve_interpret(interpret),
    )
    return rank[:m], counts[:num_buckets]


def bucket_ranks_lanes(
    keys,
    lanes,
    num_buckets: int,
    *,
    use_kernel: Optional[bool] = None,
    interpret: Optional[bool] = None,
    block_msgs: int = 1024,
):
    """Q-aware bucket ranking for the union-frontier batched data plane:
    shared stable ranks over the union key list plus the per-lane
    per-bucket membership histogram, in one sweep (the Q-aware variant of
    :func:`bucket_ranks` — see ``repro.core.routing.route_union``).

    Args:
      keys: (M,) int32 bucket per union entry in ``[0, num_buckets]``
        (``num_buckets`` = invalid sentinel).
      lanes: (M, Q) lane membership (bool/0-1) — all-False rows for
        invalid entries.
      num_buckets: static bucket count (the worker count W).
    Returns:
      (rank (M,) int32, counts (num_buckets,) int32,
       lane_counts (num_buckets, Q) int32).
    """
    keys = jnp.asarray(keys, jnp.int32)
    lanes = jnp.asarray(lanes, jnp.int32)
    if not resolve_use_kernel(use_kernel):
        return kref.bucket_ranks_lanes_ref(keys, lanes, num_buckets)
    m, q = lanes.shape
    m_pad = _round_up(max(m, 1), block_msgs)
    if m_pad != m:
        keys = jnp.concatenate(
            [keys, jnp.full((m_pad - m,), num_buckets, jnp.int32)]
        )
        lanes = jnp.concatenate(
            [lanes, jnp.zeros((m_pad - m, q), jnp.int32)]
        )
    rank, counts, lane_counts = kbucket.bucket_ranks_lanes_pallas(
        keys,
        lanes,
        num_buckets=num_buckets,
        block_msgs=block_msgs,
        interpret=resolve_interpret(interpret),
    )
    return rank[:m], counts[:num_buckets], lane_counts[:num_buckets]
