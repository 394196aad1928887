"""Dynamic message routing — the exchange beneath the paper's standard
message channels (Table I).

Messages are (destination-global-id, payload) pairs with a validity mask.
Ownership is by contiguous id range, so a routed exchange only needs
*owner order*, not full destination order: each message's wire slot is
``owner * C + rank`` (rank = stable arrival rank within the owner
bucket), the packed (W, C, ...) buffer is exchanged with one tiled
``all_to_all``. The buffer is *per-owner*: the bucket router scatters
each message directly into its destination owner's C-wide tile, and the
tiled ``all_to_all`` splits those tiles across the mesh axis — no gather
through replicated memory, and under ``shard_map`` each device ships
exactly one tile per peer. C is the caller's per-peer capacity: the
partition layer's ``route_cap`` bound (``ChannelContext.edge_capacity``)
keeps it near the real per-owner occupancy instead of the full vertex
width, which is what makes the exchange weak-scale (see
``docs/scaling.md``). Two interchangeable implementations compute the
slots:

  - ``"bucket"`` (default): one-pass counting sort — per-owner histogram
    + stable rank + scatter. O(M·W) work / O(M) depth with the worker
    count W as the one-hot lane width, so it is the win whenever W is a
    modest constant (the regime of this library; at very large W the
    comparison narrows). Backed by the Pallas kernel
    (``repro.kernels.bucket_route``) on TPU and a pure-jnp reference
    elsewhere (``repro.kernels.ops.bucket_ranks`` decides, see the
    config surface there).
  - ``"sort"``: the legacy O(M log M) stable ``argsort`` over owners —
    kept as the measured baseline (``benchmarks/channel_dataplane.py``).

Both produce **bit-identical** ``Routed`` results (same slots, same
counts, same packing), so channels and compositions are oblivious to the
choice; select per call (``impl=``), per compile
(:func:`impl_scope` — what ``Engine(route_impl=...)`` uses), or via the
``REPRO_ROUTE_IMPL`` environment variable.

Used by DirectMessage / CombinedMessage / RequestRespond; the
scatter-combine channel avoids all of this via its static plan — that gap
is exactly the optimization the paper measures.

Traffic accounting contract: ``sent_count`` counts *wire* messages —
valid entries actually packed into a peer's capacity-bounded block
(post-dedup, since deduping channels route their deduped id list).
Enqueued sends beyond the capacity latch ``overflow`` but are never
charged: they never reach the wire.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.configs import knobs
from repro.core.channel import TRAFFIC_DTYPE
from repro.kernels import ops as kops
from repro.pregel import tracing
from repro.pregel.errors import PlanRangeError

BIG = jnp.iinfo(jnp.int32).max


def _check_slot_range(w: int, capacity: int) -> None:
    """Wire slots are int32 ``owner * C + rank``: at production W x C the
    id silently wraps into another worker's range. W and C are trace-time
    python ints, so the bound is enforced before anything is compiled."""
    if w * capacity > BIG:
        raise PlanRangeError(
            f"routed exchange W * capacity = {w} * {capacity} exceeds the "
            f"int32 wire-slot range ({BIG}); reduce the per-peer capacity "
            "(e.g. a partition-derived ChannelContext.edge_capacity bound) "
            "or the worker count.",
            channels=("route",),
        )

IMPLS = ("bucket", "sort")

#: the routed-exchange implementation knob (explicit > impl_scope >
#: REPRO_ROUTE_IMPL > "bucket") — see repro.configs.knobs
ROUTE_IMPL = knobs.Knob(
    "route_impl", env="REPRO_ROUTE_IMPL", default="bucket",
    choices=IMPLS, describe="routing impl")


def resolve_impl(impl: Optional[str] = None) -> str:
    """The routing implementation for a call site: explicit argument,
    else the :func:`impl_scope` override, else ``REPRO_ROUTE_IMPL``,
    else ``"bucket"``."""
    return ROUTE_IMPL.resolve(impl)


def impl_scope(impl: Optional[str]):
    """Pin the routing impl for every route() under the scope
    (trace-time: wrap the compile, not the execution)."""
    return ROUTE_IMPL.scope(impl)


# --------------------------------------------------------------------------
# batched (query-lane) routing configuration — mirrors the impl surface
# --------------------------------------------------------------------------

BATCH_IMPLS = ("union", "lane")

#: the batched-routing strategy knob (explicit > batch_scope >
#: REPRO_ROUTE_BATCH > "union") — see repro.configs.knobs
ROUTE_BATCH = knobs.Knob(
    "route_batch", env="REPRO_ROUTE_BATCH", default="union",
    choices=BATCH_IMPLS, describe="route batch strategy")


def resolve_batch(batch: Optional[str] = None) -> str:
    """The batched-routing strategy for a call site: explicit argument,
    else the :func:`batch_scope` override, else ``REPRO_ROUTE_BATCH``,
    else ``"union"``.

      - ``"union"`` (default): per superstep, the routed channels compute
        the union frontier across the Q query lanes and run ONE
        bucket-route pass over it; payloads travel as a ``(slots, Q)``
        lane matrix with per-lane membership masks.
      - ``"lane"``: the PR-5 behavior — the query vmap batches the
        serial route, i.e. Q independent route passes per superstep.
        Kept as the measured baseline (``benchmarks/routed_batching.py``).
    """
    return ROUTE_BATCH.resolve(batch)


def batch_scope(batch: Optional[str]):
    """Pin the batched-routing strategy for every routed channel under
    the scope (trace-time: wrap the compile, not the execution) — how
    ``Engine(route_batch=...)`` threads the knob through a compile."""
    return ROUTE_BATCH.scope(batch)


def lane_live(ctx):
    """Per-lane liveness scalar for batched channel units: the runtime's
    pre-step halt vote, or constant True when none was provided (e.g. a
    hand-built test context)."""
    live = getattr(ctx, "query_live", None)
    return jnp.asarray(True) if live is None else jnp.asarray(live, bool)


@dataclasses.dataclass
class Routed:
    """Per-shard result of a routed exchange."""

    ids: jax.Array        # (W, C) int32 global dst ids received (BIG pad)
    mask: jax.Array       # (W, C) bool
    payload: Any          # pytree of (W, C, ...) arrays
    # sender-side bookkeeping for positional reply (RequestRespond):
    slot: jax.Array       # (M,) wire slot per ORIGINAL message (W*C = dropped)
    sent_count: jax.Array  # (W,) wire messages packed per peer
    overflow: jax.Array   # () bool — capacity exceeded (surfaced, not silent)


def _slots_sort(key, w: int):
    """Legacy baseline: stable argsort over owners, rank by position.
    Same (rank, count) contract as ``kops.bucket_ranks`` — validity is
    already encoded in ``key`` (invalid = the ``w`` sentinel); capacity
    is applied by the caller."""
    m = key.shape[0]
    order = jnp.argsort(key)  # stable: ties keep original order
    skey = key[order]
    bounds = jnp.searchsorted(
        skey, jnp.arange(w + 1, dtype=jnp.int32), side="left"
    ).astype(jnp.int32)
    pos = jnp.arange(m, dtype=jnp.int32)
    rank_sorted = pos - bounds[jnp.minimum(skey, w - 1)]
    # scatter ranks back to original message positions
    rank = jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted)
    return rank, bounds[1:] - bounds[:-1]


@tracing.scope(tracing.ROUTE)
def route(
    ctx,
    dst,
    valid,
    payload,
    capacity: int,
    *,
    exchange_payload=True,
    impl: Optional[str] = None,
    use_kernel: Optional[bool] = None,
):
    """Route messages to the owners of their destination vertices.

    Args:
      ctx: ChannelContext (axis/W/n_loc).
      dst: (M,) int32 global destination ids.
      valid: (M,) bool.
      payload: pytree of (M, ...) arrays (may be empty dict).
      capacity: per-peer slot capacity C.
      impl: "bucket" | "sort" | None (resolve via scope/env/default).
      use_kernel: kernel-vs-reference for the bucket path (None = config).
    Returns:
      Routed — received ids/mask/payload plus sender bookkeeping.
    """
    W, n_loc, ax = ctx.num_workers, ctx.n_loc, ctx.axis
    c = capacity
    _check_slot_range(W, c)
    ids = jnp.where(valid, dst.astype(jnp.int32), BIG)
    owner = jnp.clip(ids // n_loc, 0, W - 1)
    key = jnp.where(valid, owner, W).astype(jnp.int32)

    if resolve_impl(impl) == "bucket":
        rank, count = kops.bucket_ranks(key, W, use_kernel=use_kernel)
    else:
        rank, count = _slots_sort(key, W)

    fits = rank < c
    overflow = jnp.any(valid & ~fits)
    slot = jnp.where(valid & fits, key * c + rank, W * c)
    # wire accounting: only packed messages cross the wire
    sent_count = jnp.minimum(count, c)

    def pack(leaf, fill):
        shape = (W * c + 1,) + leaf.shape[1:]
        buf = jnp.full(shape, fill, leaf.dtype)
        return buf.at[slot].set(leaf, mode="drop")[: W * c]

    send_ids = pack(ids, BIG).reshape(W, c)
    with tracing.scope(tracing.EXCHANGE):
        recv_ids = jax.lax.all_to_all(send_ids, ax, 0, 0, tiled=True)
        recv_mask = recv_ids != BIG

    if exchange_payload:
        def xch(leaf):
            packed = pack(leaf, 0).reshape((W, c) + leaf.shape[1:])
            with tracing.scope(tracing.EXCHANGE):
                return jax.lax.all_to_all(packed, ax, 0, 0, tiled=True)
        recv_payload = jax.tree_util.tree_map(xch, payload)
    else:
        recv_payload = None

    return Routed(
        ids=recv_ids,
        mask=recv_mask,
        payload=recv_payload,
        slot=slot,
        sent_count=sent_count,
        overflow=overflow,
    )


# --------------------------------------------------------------------------
# union-frontier batched routing (the query-aware data plane)
#
# Under the batched query plane the step function is vmapped over Q query
# lanes INSIDE the worker mapping, so a naive routed channel runs Q
# independent bucket-route passes over mostly-overlapping frontiers. The
# units below escape that vmap with ``jax.custom_batching.custom_vmap``:
# the batching rule sees all Q lanes materialized at once (batch at axis
# 0) while still under the worker trace, computes the UNION frontier,
# runs ONE bucket-route pass over it, and exchanges payloads as a
# ``(slots, Q)`` lane matrix with per-lane membership masks — one
# ``all_to_all`` per leaf instead of Q.
#
# Exactness contract: per-lane deliveries, ``sent_count`` and traffic are
# bit-identical to Q independent serial routes whenever the union pass
# does not overflow (union arrival ranks dominate per-lane ranks, so
# batched ``overflow`` is a conservative superset of serial overflow —
# never a silent drop). ``slot`` keeps its positional-reply semantics but
# holds *shared* wire slots, which differ from a lane's private ranks;
# only ``reply()`` consumes it and the round trip is order-exact.
# --------------------------------------------------------------------------


@tracing.scope(tracing.ROUTE)
def union_dedup(dst_l, valid_l, n_total: int, u_cap: int):
    """:func:`dedup_dense` across Q lanes at once: the compact ascending
    unique list over the UNION of every lane's valid destinations.

    Args:
      dst_l: (Q, M) int32 global destination ids per lane.
      valid_l: (Q, M) bool.
      n_total: static id-space bound (W * n_loc).
      u_cap: compact-list capacity — ``min(Q * M, n_total)`` never
        truncates (the union cannot exceed either bound).
    Returns:
      (u_dst (u_cap,) ascending BIG-padded, pos (N,) compact index per id).
    """
    key_l = jnp.where(valid_l, dst_l.astype(jnp.int32), n_total)
    got = (
        jnp.zeros((n_total,), jnp.int32)
        .at[key_l.reshape(-1)]
        .add(1, mode="drop")
        > 0
    )
    pos = jnp.cumsum(got.astype(jnp.int32)) - 1
    u_dst = (
        jnp.full((u_cap + 1,), BIG, jnp.int32)
        .at[jnp.where(got, pos, u_cap)]
        .set(jnp.arange(n_total, dtype=jnp.int32), mode="drop")[:u_cap]
    )
    return u_dst, pos


def union_ranks(key, lanes, w: int, impl: Optional[str] = None,
                use_kernel: Optional[bool] = None):
    """Shared ranks + per-lane per-bucket counts over a union key list —
    the one route pass of the batched data plane. Same (rank, count)
    contract as the serial pass; ``lane_counts`` (W, Q) attributes wire
    occupancy to each lane for per-query traffic accounting."""
    if resolve_impl(impl) == "bucket":
        return kops.bucket_ranks_lanes(key, lanes, w, use_kernel=use_kernel)
    rank, count = _slots_sort(key, w)
    lane_counts = jax.ops.segment_sum(
        jnp.asarray(lanes, jnp.int32), key, w + 1)[:w]
    return rank, count, lane_counts


@tracing.scope(tracing.ROUTE)
def route_union(
    ctx,
    dst,
    valid,
    payload,
    capacity: int,
    *,
    exchange_payload=True,
    impl: Optional[str] = None,
    use_kernel: Optional[bool] = None,
):
    """Batched :func:`route`: one shared bucket-route pass over the union
    frontier of all Q query lanes (see the section comment above).

    Call it exactly like ``route`` from inside a batched step (per-lane
    (M,) views); it returns the per-lane ``Routed`` view of the shared
    exchange. Positional union slots are only sound when ``dst`` is
    lane-invariant (graph topology, not query state) — proven via the
    custom_vmap ``in_batched`` flags; a lane-varying ``dst`` falls back
    to Q per-lane route passes inside the rule (same results, no
    sharing). Outside the batched query plane this IS ``route``.
    """
    if not getattr(ctx, "batched", False):
        return route(ctx, dst, valid, payload, capacity,
                     exchange_payload=exchange_payload, impl=impl,
                     use_kernel=use_kernel)
    impl = resolve_impl(impl)
    W, n_loc, ax = ctx.num_workers, ctx.n_loc, ctx.axis
    c = capacity
    _check_slot_range(W, c)
    leaves, treedef = jax.tree_util.tree_flatten(payload)

    def routed_tuple(r):
        pl_leaves = (jax.tree_util.tree_leaves(r.payload)
                     if exchange_payload else ())
        return (r.ids, r.mask, r.slot, r.sent_count, r.overflow, *pl_leaves)

    @custom_vmap
    def ex(qidx, live, dst, valid, *leaves):
        # unbatched trace (the runtime always vmaps over Q, so this body
        # only runs for a hand-called unbatched unit): the serial route
        r = route(ctx, dst, valid & live, treedef.unflatten(list(leaves)),
                  c, exchange_payload=exchange_payload, impl=impl,
                  use_kernel=use_kernel)
        return routed_tuple(r)

    @ex.def_vmap
    def _rule(axis_size, in_batched, qidx, live, dst, valid, *leaves):
        q = axis_size
        _, lb, db, vb = in_batched[:4]
        leaf_b = in_batched[4:]
        live2 = live if lb else jnp.broadcast_to(live, (q,))
        valid2 = valid if vb else jnp.broadcast_to(valid, (q,) + valid.shape)
        valid_eff = valid2 & live2[:, None]  # (Q, M)
        leaves2 = tuple(
            lf if b else jnp.broadcast_to(lf, (q,) + lf.shape)
            for lf, b in zip(leaves, leaf_b))

        if db:
            # dst varies per lane: positional sharing is unsound — run Q
            # per-lane serial routes (bit-identical, no union win)
            def one(d, v, lvs):
                r = route(ctx, d, v, treedef.unflatten(list(lvs)), c,
                          exchange_payload=exchange_payload, impl=impl,
                          use_kernel=use_kernel)
                return routed_tuple(r)

            outs = jax.vmap(one)(dst, valid_eff, leaves2)
            return outs, tuple(True for _ in outs)

        # ---- one shared pass over the union frontier ----
        uvalid = jnp.any(valid_eff, axis=0)  # (M,)
        ids = jnp.where(uvalid, dst.astype(jnp.int32), BIG)
        owner = jnp.clip(ids // n_loc, 0, W - 1)
        key = jnp.where(uvalid, owner, W).astype(jnp.int32)
        lanes = valid_eff.T  # (M, Q)
        rank, count, lane_counts = union_ranks(
            key, lanes, W, impl=impl, use_kernel=use_kernel)
        fits = rank < c
        packed = uvalid & fits
        slot = jnp.where(packed, key * c + rank, W * c)  # (M,) shared
        # per-lane views of the shared pass: overflow is conservative
        # (union ranks dominate lane ranks); sent counts are exact
        overflow_l = jnp.any(valid_eff & ~fits[None, :], axis=1)  # (Q,)
        sent_l = jnp.minimum(lane_counts, c).T  # (Q, W)
        slot_l = jnp.where(valid_eff & packed[None, :], slot[None, :], W * c)

        def pack(leafT, fill):  # leafT (M, ...) scattered at shared slots
            shape = (W * c + 1,) + leafT.shape[1:]
            buf = jnp.full(shape, fill, leafT.dtype)
            return buf.at[slot].set(leafT, mode="drop")[: W * c]

        send_ids = pack(ids, BIG).reshape(W, c)
        with tracing.scope(tracing.EXCHANGE):
            recv_ids = jax.lax.all_to_all(send_ids, ax, 0, 0, tiled=True)
        # per-lane wire membership rides as one (slots, Q) lane matrix
        send_mask = pack(lanes, False).reshape(W, c, q)
        with tracing.scope(tracing.EXCHANGE):
            recv_mask = jax.lax.all_to_all(send_mask, ax, 0, 0, tiled=True)
            out_mask = jnp.moveaxis(recv_mask, 2, 0)  # (Q, W, c)
            # a lane's ids view pads slots it did not occupy (= serial view)
            out_ids = jnp.where(out_mask, recv_ids[None], BIG)

        out = [out_ids, out_mask, slot_l, sent_l, overflow_l]
        if exchange_payload:
            for leaf2 in leaves2:  # (Q, M, ...)
                leafT = jnp.moveaxis(leaf2, 0, 1)  # (M, Q, ...)
                sel = lanes.reshape(lanes.shape + (1,) * (leafT.ndim - 2))
                leafT = jnp.where(sel, leafT, 0)  # serial pack fill
                buf = pack(leafT, 0).reshape((W, c, q) + leafT.shape[2:])
                with tracing.scope(tracing.EXCHANGE):
                    recv = jax.lax.all_to_all(buf, ax, 0, 0, tiled=True)
                    out.append(jnp.moveaxis(recv, 2, 0))  # (Q, W, c, ...)
        return tuple(out), tuple(True for _ in out)

    outs = ex(ctx.query_index, lane_live(ctx),
              jnp.asarray(dst, jnp.int32), valid, *leaves)
    ids, mask, slot, sent_count, overflow = outs[:5]
    recv_payload = (treedef.unflatten(list(outs[5:]))
                    if exchange_payload else None)
    return Routed(ids=ids, mask=mask, payload=recv_payload, slot=slot,
                  sent_count=sent_count, overflow=overflow)


@tracing.scope(tracing.EXCHANGE)
def reply(ctx, routed: Routed, resp):
    """Send per-slot responses back (positionally — no ids on the wire)
    and deliver them in the original message order.

    Args:
      routed: the Routed from the request phase.
      resp: pytree of (W, C, ...) responses aligned with routed.ids.
    Returns:
      pytree of (M, ...) responses in original message order (zeros for
      messages that were never packed).
    """
    ax = ctx.axis

    def xch_back(leaf):
        back = jax.lax.all_to_all(leaf, ax, 0, 0, tiled=True)  # (W, C, ...)
        flat = back.reshape((-1,) + leaf.shape[2:])
        flat = jnp.concatenate([flat, jnp.zeros_like(flat[:1])], axis=0)
        # routed.slot is per original message: dropped slots hit the pad row
        return flat[jnp.minimum(routed.slot, flat.shape[0] - 1)]

    return jax.tree_util.tree_map(xch_back, resp)


def remote_count(ctx, sent_count):
    """Wire messages that actually cross a worker boundary (exclude self)."""
    me = ctx.me()
    return (sent_count.sum() - sent_count[me]).astype(TRAFFIC_DTYPE)


@tracing.scope(tracing.ROUTE)
def dedup_dense(dst, valid, n_total: int, m_cap: Optional[int] = None):
    """Sort-free per-worker dedup: the compact ascending list of unique
    valid destinations, via a dense occupancy histogram + prefix-sum
    compaction (O(M + N) with an int32 N-sized transient — the counting
    idea of the bucket route applied to the id space; callers reduce
    values in the *compact* space, never densely).

    Regime note: the O(N) term is over the *global* id space, so it does
    not shrink as workers are added, while M = E/W does. Counting dedup
    wins whenever N is within a small factor of M (graphs with average
    degree >= ~2, the regime of this library and its benchmarks); for
    W*N >> E a sorted dedup would be the better trade — a future lever,
    switchable on the static (m, n_total) shapes at trace time.

    Args:
      dst: (M,) int32 global destination ids.
      valid: (M,) bool.
      n_total: static id-space bound (W * n_loc).
      m_cap: compact-list capacity (default M; the unique count never
        exceeds the valid count, so M is always safe).
    Returns:
      (u_dst, pos): ``u_dst`` (m_cap,) the unique destinations in
      ascending order, padded with BIG; ``pos`` (N,) the compact index of
      each destination id (arbitrary where the id never occurs).
    """
    m = dst.shape[0]
    m_cap = m if m_cap is None else m_cap
    key = jnp.where(valid, dst.astype(jnp.int32), n_total)
    got = jnp.zeros((n_total,), jnp.int32).at[key].add(1, mode="drop") > 0
    pos = jnp.cumsum(got.astype(jnp.int32)) - 1  # compact index per id
    u_dst = (
        jnp.full((m_cap + 1,), BIG, jnp.int32)
        .at[jnp.where(got, pos, m_cap)]
        .set(jnp.arange(n_total, dtype=jnp.int32), mode="drop")[:m_cap]
    )
    return u_dst, pos
