"""Pure-jnp oracles for the Pallas kernels in this package."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import combiners as cb


def segment_combine_ref(vals, seg_ids, num_segments, combiner):
    """Segment reduction oracle.

    Args:
      vals: (E, D) values (padded entries must carry the combiner identity
        or a seg_id >= num_segments).
      seg_ids: (E,) int32 destination segment per value.
      num_segments: static int, number of output rows.
      combiner: repro.core.combiners.Combiner or name.
    Returns:
      (num_segments, D) combined values; empty segments hold the identity.
    """
    combiner = cb.get(combiner)
    seg_ids = jnp.asarray(seg_ids, jnp.int32)
    # Entries with seg >= num_segments are dropped by segment_* semantics
    # (indices out of range are ignored in jax.ops.segment_* with
    # indices_are_sorted=False and num_segments given).
    return combiner.segment_reduce(vals, seg_ids, num_segments)


def bucket_ranks_ref(keys, num_buckets):
    """Stable counting-scatter oracle for the bucket-route kernel.

    Args:
      keys: (M,) int32 bucket per message in ``[0, num_buckets]`` —
        ``num_buckets`` itself is the invalid/dropped sentinel.
      num_buckets: static int B (e.g. the worker count W).
    Returns:
      (rank, counts) — ``rank[i]`` is the arrival rank of message ``i``
      within its bucket (stable: original order preserved), ``counts``
      is the (B,) occupancy histogram over the real buckets.

    O(M·B) work via an (M, B+1) one-hot cumsum — the intended regime is
    B = the worker count, a modest constant, where this is a pure win
    over the O(M log M) argsort it replaces (see ``core/routing.py``).
    """
    keys = jnp.asarray(keys, jnp.int32)
    onehot = (
        keys[:, None] == jnp.arange(num_buckets + 1, dtype=jnp.int32)[None, :]
    ).astype(jnp.int32)
    rank = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - 1, keys[:, None], axis=1
    )[:, 0]
    return rank, onehot[:, :num_buckets].sum(axis=0)


def bucket_ranks_lanes_ref(keys, lanes, num_buckets):
    """Q-aware oracle for the union-frontier bucket route: the *shared*
    stable ranks/occupancy over the union key list (identical to
    :func:`bucket_ranks_ref`) plus the per-lane per-bucket membership
    histogram — the quantity the batched data plane needs to attribute
    wire traffic to each query lane without a second pass.

    Args:
      keys: (M,) int32 bucket per union entry in ``[0, num_buckets]``
        (``num_buckets`` = invalid sentinel).
      lanes: (M, Q) lane membership (bool or 0/1 int) — lane q enqueued
        the entry. Membership of an invalid entry must be all-False.
      num_buckets: static int B (the worker count W).
    Returns:
      (rank (M,), counts (B,), lane_counts (B, Q)) — ``lane_counts[b, q]``
      is how many of lane q's entries landed in bucket b.
    """
    keys = jnp.asarray(keys, jnp.int32)
    rank, counts = bucket_ranks_ref(keys, num_buckets)
    lane_counts = jax.ops.segment_sum(
        jnp.asarray(lanes, jnp.int32), keys, num_buckets + 1
    )[:num_buckets]
    return rank, counts, lane_counts
