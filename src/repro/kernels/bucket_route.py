"""Pallas TPU kernel: one-pass stable bucket ranking (the routing hot loop).

Ownership in this library is by contiguous vertex-id range, so a routed
exchange only needs *owner order*, not full destination order: a message's
wire slot is ``owner * C + rank`` where ``rank`` is its stable arrival
rank within the owner bucket. That rank is a counting sort — O(M) against
the O(M log M) ``argsort`` it replaces — and maps onto the TPU as a
single sequential sweep over message chunks:

  - the message keys (owner per message, already clipped; ``B`` = invalid
    sentinel) are tiled into chunks of ``block_msgs``, each laid out
    lane-dense as ``(rows, lanes)`` in message order (``lanes`` =
    ``min(block_msgs, 128)``), so no key is padded out to a lane row,
  - a ``(1, B + 1)`` running-occupancy row lives in the revisited counts
    output (the canonical Pallas accumulator pattern: initialized at grid
    step 0, read-modify-written by every step),
  - inside a chunk, per bucket, a message's arrival rank is the count of
    the bucket's earlier messages in its own row — an inclusive prefix
    along the lanes, taken as one matmul with an upper-triangular ones
    matrix on the MXU (0/1 operands, float32 accumulation: exact) — plus
    the bucket's count in the earlier rows, plus the occupancy carried in
    from the previous chunks.

Grid: ``(num_chunks,)``, iterated sequentially on one core — exactly the
property that makes the running counts carry correct. The actual scatter
into the ``(W, C, ...)`` send buffer stays outside the kernel (a plain
``.at[slot].set``): the expensive part of the routing was never the
scatter, it was computing the permutation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.layout import chunk_tile, col_to_row


def _chunk_ranks(keys, counts_ref, per_bucket=None):
    """Stable ranks of one ``(R, L)`` key chunk; advances the running
    occupancy row and calls ``per_bucket(b, onehot)`` for each bucket."""
    r, l = keys.shape
    upper = (jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
             ).astype(jnp.float32)
    before = (jax.lax.broadcasted_iota(jnp.int32, (r, r), 1)
              < jax.lax.broadcasted_iota(jnp.int32, (r, r), 0))
    rank = jnp.zeros((r, l), jnp.int32)
    for b in range(counts_ref.shape[1]):
        hit = keys == b
        onehot = hit.astype(jnp.float32)
        # inclusive count of bucket b along each row, on the MXU
        in_row = jnp.dot(onehot, upper, preferred_element_type=jnp.float32)
        row_tot = jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)
        # bucket b's count in all earlier rows of the chunk
        earlier = jnp.sum(jnp.where(before, col_to_row(row_tot), 0),
                          axis=1, keepdims=True)
        base = counts_ref[:, b:b + 1]
        rank = jnp.where(hit, in_row.astype(jnp.int32) - 1 + earlier + base,
                         rank)
        counts_ref[:, b:b + 1] = base + jnp.sum(row_tot, keepdims=True)
        if per_bucket is not None:
            per_bucket(b, onehot)
    return rank


def _kernel(key_ref, rank_ref, counts_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    rank_ref[...] = _chunk_ranks(key_ref[...], counts_ref)


def _kernel_lanes(key_ref, lane_ref, rank_ref, counts_ref, lane_counts_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)
        lane_counts_ref[...] = jnp.zeros_like(lane_counts_ref)

    def lane_histogram(b, onehot):
        # per-lane count of bucket b over this chunk
        for q in range(lane_ref.shape[0]):
            member = lane_ref[q].astype(jnp.float32)  # (R, L) 0/1
            n = jnp.sum(onehot * member, keepdims=True).astype(jnp.int32)
            lane_counts_ref[b:b + 1, q:q + 1] += n

    rank_ref[...] = _chunk_ranks(key_ref[...], counts_ref, lane_histogram)


def bucket_ranks_pallas(
    keys,
    *,
    num_buckets: int,
    block_msgs: int = 1024,
    interpret: bool = True,
):
    """Stable per-bucket arrival ranks via a sequential counting sweep.

    Args:
      keys: (M_pad,) int32 bucket per message in ``[0, num_buckets]``;
        ``num_buckets`` is the invalid sentinel (still ranked, so padded
        tails are harmless). ``M_pad`` must be a multiple of
        ``block_msgs``.
      num_buckets: static bucket count B (the worker count).
      block_msgs: chunk length per grid step (at most 128, or a multiple
        of 128).
    Returns:
      (rank, counts): (M_pad,) int32 stable rank within bucket and the
      (B + 1,) final occupancy histogram (sentinel bucket last).
    """
    m = keys.shape[0]
    assert m % block_msgs == 0, (m, block_msgs)
    r, l = chunk_tile(block_msgs)
    nc = m // block_msgs
    tile = pl.BlockSpec((pl.squeezed, r, l), lambda i: (i, 0, 0))
    rank, counts = pl.pallas_call(
        _kernel,
        grid=(nc,),
        in_specs=[tile],
        out_specs=(tile,
                   pl.BlockSpec((1, num_buckets + 1), lambda i: (0, 0))),
        out_shape=(
            jax.ShapeDtypeStruct((nc, r, l), jnp.int32),
            jax.ShapeDtypeStruct((1, num_buckets + 1), jnp.int32),
        ),
        interpret=interpret,
    )(jnp.asarray(keys, jnp.int32).reshape(nc, r, l))
    return rank.reshape(m), counts[0]


def bucket_ranks_lanes_pallas(
    keys,
    lanes,
    *,
    num_buckets: int,
    block_msgs: int = 1024,
    interpret: bool = True,
):
    """Q-aware bucket ranking: the same sequential counting sweep as
    :func:`bucket_ranks_pallas`, fused with the per-lane per-bucket
    membership histogram the batched (union-frontier) data plane charges
    traffic from — one pass over the union key list instead of Q.

    Args:
      keys: (M_pad,) int32 bucket per union entry in ``[0, num_buckets]``
        (``num_buckets`` = invalid sentinel); M_pad a ``block_msgs``
        multiple.
      lanes: (M_pad, Q) int32 lane membership (0/1); padded tail rows
        must be all-zero. The kernel reads it query-major, lane-dense.
    Returns:
      (rank (M_pad,), counts (B + 1,), lane_counts (B + 1, Q)).
    """
    m = keys.shape[0]
    q = lanes.shape[1]
    assert m % block_msgs == 0, (m, block_msgs)
    assert lanes.shape[0] == m, (lanes.shape, m)
    r, l = chunk_tile(block_msgs)
    nc = m // block_msgs
    tile = pl.BlockSpec((pl.squeezed, r, l), lambda i: (i, 0, 0))
    rank, counts, lane_counts = pl.pallas_call(
        _kernel_lanes,
        grid=(nc,),
        in_specs=[
            tile,
            pl.BlockSpec((q, pl.squeezed, r, l), lambda i: (0, i, 0, 0)),
        ],
        out_specs=(
            tile,
            pl.BlockSpec((1, num_buckets + 1), lambda i: (0, 0)),
            pl.BlockSpec((num_buckets + 1, q), lambda i: (0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((nc, r, l), jnp.int32),
            jax.ShapeDtypeStruct((1, num_buckets + 1), jnp.int32),
            jax.ShapeDtypeStruct((num_buckets + 1, q), jnp.int32),
        ),
        interpret=interpret,
    )(
        jnp.asarray(keys, jnp.int32).reshape(nc, r, l),
        jnp.asarray(lanes, jnp.int32).T.reshape(q, nc, r, l),
    )
    return rank.reshape(m), counts[0], lane_counts
