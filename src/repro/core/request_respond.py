"""Request-Respond channel (paper §IV-C2).

Every vertex may request an attribute of any other vertex. The channel
dedups requests to the same destination per worker (a counting
prefix-sum compaction — ``routing.dedup_dense``, no sort), sends only
unique ids, and the responder replies with a *positionally ordered value
list* — no ids on the respond wire. This is the paper's fix for the
respond-phase imbalance caused by high-degree vertices, plus its byte
trick (reply in request order). Traffic is charged per *wire* message:
the post-dedup unique ids on the request wire, the positional values on
the respond wire.

Registry contract (fused runtime): the channel contributes two fixed stat
keys — ``<name>/request`` and ``<name>/respond`` — on every trace, even
when no request is valid (zero traffic, not a missing key).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap

from repro.core import routing
from repro.core.channel import ChannelContext
from repro.pregel import tracing


def _request_core(ctx, dst, valid, rv, capacity, name):
    """The serial request/respond body (also the per-lane body under
    ``route_batch="lane"``). Returns (out (R, D), overflow (), remote ())
    — traffic is charged by the caller."""
    d = rv.shape[-1]
    r = dst.shape[0]
    n_total = ctx.num_workers * ctx.n_loc

    with tracing.channel(name + "/request"):
        # --- dedup: one compact entry per unique destination (sort-free)
        u_dst, pos = routing.dedup_dense(dst, valid, n_total)
        u_valid = u_dst != routing.BIG

        # --- request phase: ids only ---
        routed = routing.route(ctx, u_dst, u_valid, {}, capacity)
        remote = routing.remote_count(ctx, routed.sent_count)

    with tracing.channel(name + "/respond"):
        # --- respond phase: positional values, no ids ---
        lidx = jnp.where(routed.mask, routed.ids - ctx.me() * ctx.n_loc,
                         ctx.n_loc)
        rv_pad = jnp.concatenate([rv, jnp.zeros((1, d), rv.dtype)], axis=0)
        resp = rv_pad[jnp.clip(lidx, 0, ctx.n_loc)]  # (W, C, D)
        back = routing.reply(ctx, routed, {"v": resp})["v"]  # unique rows

        # --- expand to all requests: each gathers its unique row ---
        with tracing.scope(tracing.EXCHANGE):
            idx = pos[jnp.clip(dst.astype(jnp.int32), 0, n_total - 1)]
            per_req = back[jnp.clip(idx, 0, max(r - 1, 0))]
            out = jnp.where(valid[:, None], per_req, 0)
    return out, routed.overflow, remote


def _request_union(ctx, dst, valid, rv, capacity, name):
    """Request/respond across Q query lanes with ONE dedup + route pass
    over the union of the lanes' request sets. Unique ids cross the wire
    once per worker pair regardless of how many lanes ask; responses come
    back as a positional (slots, Q·D) lane matrix, and each lane gathers
    only the rows it asked for. Pure gather — bit-identical to the serial
    body per lane whenever the union pass does not overflow."""
    W, n_loc, ax = ctx.num_workers, ctx.n_loc, ctx.axis
    n_total = W * n_loc
    r = dst.shape[0]
    d = rv.shape[-1]
    c = capacity
    impl = routing.resolve_impl(None)

    @custom_vmap
    def ex(qidx, live, dst, valid, rv):
        return _request_core(ctx, dst, valid & live, rv, c, name)

    @ex.def_vmap
    def _rule(axis_size, in_batched, qidx, live, dst, valid, rv):
        q = axis_size
        _, lb, db, vb, rb = in_batched
        live2 = live if lb else jnp.broadcast_to(live, (q,))
        valid2 = valid if vb else jnp.broadcast_to(valid, (q, r))
        valid_eff = valid2 & live2[:, None]  # (Q, R)
        dst2 = (dst if db else jnp.broadcast_to(dst, (q, r))).astype(jnp.int32)
        rv2 = rv if rb else jnp.broadcast_to(rv, (q, n_loc, d))

        with tracing.channel(name + "/request"), \
                tracing.scope(tracing.ROUTE):
            # ---- union dedup: one compact entry per unique id ANY lane asks
            u_cap = min(q * r, n_total)
            u_dst, pos = routing.union_dedup(dst2, valid_eff, n_total, u_cap)
            u_valid = u_dst != routing.BIG
            seg_l = jnp.where(  # (Q, R)
                valid_eff, pos[jnp.clip(dst2, 0, n_total - 1)], u_cap)
            lane_has = (
                jnp.zeros((q, u_cap + 1), jnp.int32)
                .at[jnp.arange(q)[:, None], seg_l]
                .add(1)[:, :u_cap]
                > 0
            )  # (Q, u_cap)

            # ---- ONE route pass over the union unique list ----
            owner_u = jnp.clip(u_dst // n_loc, 0, W - 1)
            key_u = jnp.where(u_valid, owner_u, W).astype(jnp.int32)
            lanes = lane_has.T  # (u_cap, Q)
            rank, count, lane_counts = routing.union_ranks(
                key_u, lanes, W, impl=impl)
            fits = rank < c
            packed = u_valid & fits
            slot = jnp.where(packed, key_u * c + rank, W * c)
            ovf_l = jnp.any(lane_has & ~fits[None, :], axis=1)  # (Q,)
            sent_l = jnp.minimum(lane_counts, c)  # (W, Q)
            me = jax.lax.axis_index(ax)
            remote_l = (sent_l.sum(axis=0) - sent_l[me]).astype(
                routing.TRAFFIC_DTYPE)  # (Q,)

            # ---- request wire: shared unique ids, one all_to_all ----
            ids_buf = jnp.full((W * c + 1,), routing.BIG, jnp.int32)
            ids_buf = ids_buf.at[slot].set(u_dst, mode="drop")[: W * c]
            with tracing.scope(tracing.EXCHANGE):
                recv_ids = jax.lax.all_to_all(
                    ids_buf.reshape(W, c), ax, 0, 0, tiled=True)  # (W, C)

        with tracing.channel(name + "/respond"):
            # ---- respond wire: positional (slots, Q, D) lane matrix ----
            lidx = jnp.where(
                recv_ids != routing.BIG, recv_ids - me * n_loc, n_loc)
            rv_pad = jnp.concatenate(  # (Q, n_loc+1, D)
                [rv2, jnp.zeros((q, 1, d), rv2.dtype)], axis=1)
            resp = rv_pad[:, jnp.clip(lidx, 0, n_loc)]  # (Q, W, C, D)
            with tracing.scope(tracing.EXCHANGE):
                back = jax.lax.all_to_all(  # (W, C, Q, D)
                    jnp.moveaxis(resp, 0, 2), ax, 0, 0, tiled=True)
                flat = jnp.concatenate(
                    [back.reshape(W * c, q, d),
                     jnp.zeros((1, q, d), rv2.dtype)], 0)
                back_u = flat[jnp.minimum(slot, W * c)]  # (u_cap, Q, D)

                # ---- each lane gathers its own requests' unique rows ----
                idx_l = jnp.clip(seg_l, 0, max(u_cap - 1, 0))  # (Q, R)
                per_req = back_u[idx_l, jnp.arange(q)[:, None]]  # (Q, R, D)
                out = jnp.where(valid_eff[:, :, None], per_req, 0)
        return (out, ovf_l, remote_l), (True, True, True)

    return ex(ctx.query_index, routing.lane_live(ctx),
              jnp.asarray(dst, jnp.int32), valid, rv)


@tracing.channel_call
def request(
    ctx: ChannelContext,
    dst: jax.Array,
    valid: jax.Array,
    respond_vals: jax.Array,
    capacity: int,
    *,
    name: str = "request_respond",
):
    """Request `respond_vals[dst]` for each valid request.

    Args:
      dst: (R,) int32 global ids to query.
      valid: (R,) bool.
      respond_vals: (n_loc,) or (n_loc, D) — the per-vertex attribute the
        responder exposes (the paper's user-provided f(vertex)).
      capacity: per-peer unique-request capacity.
    Returns:
      (resp (R,[D]), overflow) — responses aligned with `dst` (zeros for
      invalid requests).
    """
    squeeze = respond_vals.ndim == 1
    rv = respond_vals[:, None] if squeeze else respond_vals
    d = rv.shape[-1]
    capacity = ctx.scale_capacity(name + "/request", capacity)

    full = ctx.full_name(name)
    if getattr(ctx, "batched", False) and routing.resolve_batch() == "union":
        out, overflow, remote = _request_union(ctx, dst, valid, rv, capacity,
                                               full)
    else:
        out, overflow, remote = _request_core(ctx, dst, valid, rv, capacity,
                                              full)

    ctx.add_traffic(name + "/request", remote * 4, remote)
    ctx.add_traffic(
        name + "/respond", remote * d * jnp.dtype(rv.dtype).itemsize, remote
    )
    ctx.add_overflow(name + "/request", overflow)
    return (out[:, 0] if squeeze else out), overflow
