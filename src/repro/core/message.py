"""Standard message-passing channels (paper Table I).

DirectMessage — arbitrary (dst, payload) messages; the receiver iterates
over deliveries. CombinedMessage — a combiner is applied both sender-side
(per destination, before the exchange) and receiver-side, yielding a dense
per-vertex combined value. Both use the dynamic routed exchange
(``repro.core.routing``, one-pass bucket routing by default), and both
put destination ids on the wire — the costs the optimized channels remove.

The CombinedMessage sender-side combine is sort-free: the
unique-destination list is compacted with a counting prefix-sum
(``routing.dedup_dense``) and values are reduced directly in that
compact space — O(M·W + N) work with only an int32 histogram as the
N-sized transient, no ``argsort`` anywhere on the dynamic data plane
(non-lattice combiners such as ``min_by_first`` still sort inside their
``segment_reduce``). ``id_bytes`` are charged once per *wire* message
(the post-dedup, capacity-packed sends that actually cross a worker
boundary), never per enqueued send.

Registry contract (fused runtime): every send is traced unconditionally —
an empty `valid` mask yields zero accounted traffic rather than a skipped
``add_traffic`` call, so the per-step stats pytree keeps a fixed shape.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.core import combiners as cb
from repro.core import routing
from repro.core.channel import ChannelContext, payload_width
from repro.kernels import ops as kops
from repro.pregel import tracing


@dataclasses.dataclass
class Delivery:
    """Messages delivered to this worker (flattened over peers)."""

    dst_local: jax.Array   # (K,) int32 local destination index (n_loc pad)
    payload: Any           # pytree of (K, ...) arrays
    mask: jax.Array        # (K,) bool
    overflow: jax.Array    # () bool


@tracing.scope(tracing.EXCHANGE)
def _delivery(ctx: ChannelContext, routed: routing.Routed, capacity: int):
    """Flatten a Routed into per-message local-index delivery form."""
    w, c = ctx.num_workers, capacity
    flat = jax.tree_util.tree_map(
        lambda x: x.reshape((w * c,) + x.shape[2:]), routed.payload
    )
    ids = routed.ids.reshape(-1)
    dst_local = jnp.where(
        routed.mask.reshape(-1), ids - ctx.me() * ctx.n_loc, ctx.n_loc
    ).astype(jnp.int32)
    return Delivery(
        dst_local=dst_local,
        payload=flat,
        mask=routed.mask.reshape(-1),
        overflow=routed.overflow,
    )


@tracing.channel_call
def direct_send(
    ctx: ChannelContext,
    dst: jax.Array,
    valid: jax.Array,
    payload,
    capacity: int,
    *,
    name: str = "direct_message",
    id_bytes: int = 4,
    wire_width: int = None,
) -> Delivery:
    """DirectMessage: deliver (dst, payload) messages to dst's owner.

    wire_width overrides the accounted per-message payload width (used by
    the monolithic-Pregel emulation where every message is padded to the
    program-wide maximum message type)."""
    capacity = ctx.scale_capacity(name, capacity)
    routed = _route_maybe_union(ctx, dst, valid, payload, capacity)
    remote = routing.remote_count(ctx, routed.sent_count)
    width = id_bytes + (wire_width if wire_width is not None
                        else payload_width(payload))
    ctx.add_traffic(name, remote * width, remote)
    ctx.add_overflow(name, routed.overflow)
    return _delivery(ctx, routed, capacity)


# combiners whose segment reductions are order-independent for any dtype
_UNION_EXACT_LATTICE = ("min", "max", "or")


def _union_exact(combiner, dtype) -> bool:
    """Whether the union-frontier batched path reproduces serial results
    bit for bit for this combiner: lattice ops are order-independent
    under the union's slot reordering; sum/prod only when the value dtype
    is exact (float reassociation would round differently)."""
    if combiner.name in _UNION_EXACT_LATTICE:
        return True
    return combiner.name in ("sum", "prod") and not jnp.issubdtype(
        jnp.dtype(dtype), jnp.inexact)


def _route_maybe_union(ctx, dst, valid, payload, capacity):
    """The routed-channel dispatch: the shared union-frontier pass under
    the batched query plane (``route_batch="union"``), the plain serial
    route otherwise (which the query vmap batches into Q passes)."""
    if getattr(ctx, "batched", False) and routing.resolve_batch() == "union":
        return routing.route_union(ctx, dst, valid, payload, capacity)
    return routing.route(ctx, dst, valid, payload, capacity)


def _combined_send_serial(ctx, dst, valid, v, combiner, capacity, use_kernel):
    """The serial CombinedMessage body (also the per-lane body the query
    vmap batches under ``route_batch="lane"``). Returns
    (out (n_loc, D), got (n_loc,), overflow (), remote ())."""
    m, d = v.shape
    n_total = ctx.num_workers * ctx.n_loc
    ident = combiner.ident_for(v.dtype)

    # sender-side combine, sort-free: compact the occupied destinations
    # into an ascending unique list (counting prefix-sum over the id
    # space), then reduce the values directly in that compact space —
    # the only O(N_global) transient is dedup_dense's int32 histogram;
    # values never materialize densely.
    u_dst, pos = routing.dedup_dense(dst, valid, n_total)
    u_valid = u_dst != routing.BIG
    seg = jnp.where(
        valid, pos[jnp.clip(dst.astype(jnp.int32), 0, n_total - 1)], m
    )
    u_vals = combiner.segment_reduce(v, seg, m)  # (m, d), u_dst-aligned

    routed = routing.route(
        ctx, u_dst, u_valid, {"v": u_vals}, capacity, use_kernel=use_kernel
    )
    remote = routing.remote_count(ctx, routed.sent_count)

    deliv = _delivery(ctx, routed, capacity)
    flat_v = jnp.where(deliv.mask[:, None], deliv.payload["v"], ident)
    out = kops.segment_combine(flat_v, deliv.dst_local, ctx.n_loc, combiner,
                               use_kernel=False)
    got = (
        jax.ops.segment_sum(
            deliv.mask.astype(jnp.int32), deliv.dst_local, ctx.n_loc
        )
        > 0
    )
    return out, got, routed.overflow, remote


def _combined_send_union(ctx, dst, valid, v, combiner, capacity, use_kernel):
    """CombinedMessage across Q query lanes with ONE dedup + route pass
    over the union frontier (see ``repro.core.routing.route_union`` for
    the mechanism and exactness contract). Per-lane combined values ride
    the wire as a (slots, Q·D) lane matrix; the combiner is applied per
    lane on both sides of the exchange.

    Per-lane results (out/got/remote) are bit-identical to the serial
    body whenever the union pass does not overflow and the combiner is
    union-exact (:func:`_union_exact`)."""
    W, n_loc, ax = ctx.num_workers, ctx.n_loc, ctx.axis
    n_total = W * n_loc
    m, d = v.shape
    c = capacity
    ident = combiner.ident_for(v.dtype)
    impl = routing.resolve_impl(None)

    @custom_vmap
    def ex(qidx, live, dst, valid, v):
        return _combined_send_serial(
            ctx, dst, valid & live, v, combiner, c, use_kernel)

    @ex.def_vmap
    def _rule(axis_size, in_batched, qidx, live, dst, valid, v):
        q = axis_size
        _, lb, db, vb, vvb = in_batched
        live2 = live if lb else jnp.broadcast_to(live, (q,))
        valid2 = valid if vb else jnp.broadcast_to(valid, (q, m))
        valid_eff = valid2 & live2[:, None]  # (Q, M)
        dst2 = (dst if db else jnp.broadcast_to(dst, (q, m))).astype(jnp.int32)
        v2 = v if vvb else jnp.broadcast_to(v, (q, m, d))

        # ---- union dedup over the id space (one histogram, all lanes) ----
        u_cap = min(q * m, n_total)
        with tracing.scope(tracing.ROUTE):
            u_dst, pos = routing.union_dedup(dst2, valid_eff, n_total, u_cap)
            u_valid = u_dst != routing.BIG
            # the lanes' positions in the SHARED compact space
            seg_l = jnp.where(
                valid_eff, pos[jnp.clip(dst2, 0, n_total - 1)], u_cap)
        # per-lane sender-side combine into the shared space
        u_vals = jax.vmap(
            lambda vv, ss: combiner.segment_reduce(vv, ss, u_cap)
        )(v2, seg_l)  # (Q, u_cap, D)
        with tracing.scope(tracing.ROUTE):
            # lane membership marks which unique ids each lane sends
            lane_has = (
                jnp.zeros((q, u_cap + 1), jnp.int32)
                .at[jnp.arange(q)[:, None], seg_l]
                .add(1)[:, :u_cap]
                > 0
            )  # (Q, u_cap)

            # ---- ONE bucket-route pass over the union unique list ----
            owner_u = jnp.clip(u_dst // n_loc, 0, W - 1)
            key_u = jnp.where(u_valid, owner_u, W).astype(jnp.int32)
            lanes = lane_has.T  # (u_cap, Q)
            rank, count, lane_counts = routing.union_ranks(
                key_u, lanes, W, impl=impl, use_kernel=use_kernel)
            fits = rank < c
            packed = u_valid & fits
            slot = jnp.where(packed, key_u * c + rank, W * c)
            ovf_l = jnp.any(lane_has & ~fits[None, :], axis=1)  # (Q,)
            sent_l = jnp.minimum(lane_counts, c)  # (W, Q)
            me = jax.lax.axis_index(ax)
            remote_l = (sent_l.sum(axis=0) - sent_l[me]).astype(
                routing.TRAFFIC_DTYPE)  # (Q,)

        # ---- pack + one all_to_all per leaf: ids, lane mask, lane values
        @tracing.scope(tracing.ROUTE)
        def pack(leafT, fill):  # (W, c, ...) send buffer
            shape = (W * c + 1,) + leafT.shape[1:]
            buf = jnp.full(shape, fill, leafT.dtype)
            return buf.at[slot].set(leafT, mode="drop")[: W * c].reshape(
                (W, c) + leafT.shape[1:])

        send_ids = pack(u_dst, routing.BIG)
        with tracing.scope(tracing.EXCHANGE):
            recv_ids = jax.lax.all_to_all(send_ids, ax, 0, 0, tiled=True)
        send_has = pack(lanes, False)
        with tracing.scope(tracing.EXCHANGE):
            recv_has = jax.lax.all_to_all(send_has, ax, 0, 0, tiled=True)
        vmat = jnp.where(
            lanes[:, :, None], jnp.moveaxis(u_vals, 0, 1), ident)
        send_v = pack(vmat, ident)
        with tracing.scope(tracing.EXCHANGE):
            recv_v = jax.lax.all_to_all(send_v, ax, 0, 0, tiled=True)
            flat_ids = recv_ids.reshape(-1)
            flat_has = recv_has.reshape(W * c, q)
            dst_local = jnp.where(
                flat_ids != routing.BIG, flat_ids - me * n_loc, n_loc
            ).astype(jnp.int32)

        # ---- receiver-side per-lane combine: one segment pass over Q·D
        flat_v = jnp.where(
            flat_has[:, :, None], recv_v.reshape(W * c, q, d), ident)
        out = kops.segment_combine(
            flat_v.reshape(W * c, q * d), dst_local, n_loc, combiner,
            use_kernel=False)
        out = jnp.moveaxis(out.reshape(n_loc, q, d), 0, 1)  # (Q, n_loc, D)
        got = jnp.moveaxis(
            jax.ops.segment_sum(
                flat_has.astype(jnp.int32), dst_local, n_loc) > 0,
            0, 1)  # (Q, n_loc)
        return (out, got, ovf_l, remote_l), (True, True, True, True)

    return ex(ctx.query_index, routing.lane_live(ctx),
              jnp.asarray(dst, jnp.int32), valid, v)


@tracing.channel_call
def combined_send(
    ctx: ChannelContext,
    dst: jax.Array,
    valid: jax.Array,
    vals: jax.Array,
    combiner,
    capacity: int,
    *,
    name: str = "combined_message",
    use_kernel: Optional[bool] = None,
    wire_width: int = None,
):
    """CombinedMessage: sender-side combine per destination, route, then
    receiver-side combine to a dense (n_loc, D) array.

    Under the batched query plane (``route_batch="union"``) the dedup +
    route happen once over the union frontier of all Q lanes, provided
    the combiner is union-exact; otherwise the serial body runs per lane.

    Returns (combined (n_loc,[D]), got_any (n_loc,) bool, overflow).
    """
    combiner = cb.get(combiner)
    capacity = ctx.scale_capacity(name, capacity)
    squeeze = vals.ndim == 1
    v = vals[:, None] if squeeze else vals
    d = v.shape[1]

    if (getattr(ctx, "batched", False)
            and routing.resolve_batch() == "union"
            and _union_exact(combiner, v.dtype)):
        out, got, overflow, remote = _combined_send_union(
            ctx, dst, valid, v, combiner, capacity, use_kernel)
    else:
        out, got, overflow, remote = _combined_send_serial(
            ctx, dst, valid, v, combiner, capacity, use_kernel)

    width = 4 + (wire_width if wire_width is not None
                 else d * jnp.dtype(v.dtype).itemsize)
    ctx.add_traffic(name, remote * width, remote)
    ctx.add_overflow(name, overflow)
    return (out[:, 0] if squeeze else out), got, overflow


@tracing.channel_call
def monolithic_send(
    ctx: ChannelContext,
    dst: jax.Array,
    valid: jax.Array,
    payload,
    capacity: int,
    *,
    pad_width: int,
    name: str = "pregel_message",
) -> Delivery:
    """Pregel-monolithic emulation (Table IV baseline): every message is
    padded to the program-wide maximum message width `pad_width`, and no
    per-channel combiner can be applied."""
    capacity = ctx.scale_capacity(name, capacity)
    routed = _route_maybe_union(ctx, dst, valid, payload, capacity)
    remote = routing.remote_count(ctx, routed.sent_count)
    ctx.add_traffic(name, remote * (4 + pad_width), remote)
    ctx.add_overflow(name, routed.overflow)
    return _delivery(ctx, routed, capacity)
