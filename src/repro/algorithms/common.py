"""Shared building blocks for the vertex-centric algorithms.

Includes the *baseline* (unoptimized, Pregel-style) implementations of the
patterns the optimized channels replace — these are what the paper's
Tables IV–VII compare against:

  - ``direct_request_respond``: 2-phase request/respond with DirectMessage
    (ids on both wires, no dedup) — what Pregel does without the
    request-respond channel;
  - ``pj_converge``: pointer-jumping loop to convergence (used inside
    Boruvka), with channel-selectable RR implementation.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import message as msg
from repro.core import request_respond as rr
from repro.core import routing
from repro.core.channel import TRAFFIC_DTYPE, ChannelContext
from repro.pregel import tracing


@tracing.channel_call
def direct_request_respond(
    ctx: ChannelContext,
    dst: jax.Array,
    valid: jax.Array,
    respond_vals: jax.Array,
    *,
    name: str = "basic_reqresp",
    wire_width: int = None,
    tags=None,
):
    """Baseline request-respond: requests via DirectMessage, responder
    replies per-request via DirectMessage (ids on both wires, no dedup).

    dst: (R,) requested global ids. If R == n_loc, request i is made by
    local vertex i (the reply routes back by vertex id). Otherwise pass
    `tags` (unique per worker, < R) so replies can be matched to requests
    (e.g. one request per edge) — the tag rides both wires, as it would in
    a real Pregel program.
    respond_vals: (n_loc,[D]) attribute exposed by every vertex.
    Returns (resp (R,[D]), overflow).
    """
    n_loc, w = ctx.n_loc, ctx.num_workers
    squeeze = respond_vals.ndim == 1
    rv = respond_vals[:, None] if squeeze else respond_vals
    d = rv.shape[-1]
    me = ctx.me()
    r = dst.shape[0]
    if tags is None:
        assert r == n_loc, "pass tags for non-per-vertex requests"
        tags = jnp.arange(n_loc, dtype=jnp.int32)
        requester = me * n_loc + tags
        tagged = False
    else:
        requester = jnp.broadcast_to(me * n_loc, (r,)).astype(jnp.int32)
        # reply is routed to any of our vertices; the tag does the matching
        tagged = True

    # phase 1: requests carry the requester id (+ tag) — no dedup.
    payload = {"requester": requester}
    if tagged:
        payload["tag"] = jnp.asarray(tags, jnp.int32)
    deliv = msg.direct_send(
        ctx, dst, valid, payload, capacity=r,
        name=name + "/request", wire_width=wire_width,
    )
    # phase 2: respond to each request individually.
    with tracing.channel(ctx.full_name(name + "/respond")):
        tgt_vals = jnp.concatenate([rv, jnp.zeros((1, d), rv.dtype)], 0)[
            jnp.clip(deliv.dst_local, 0, n_loc)
        ]  # (W*C, D) value of the requested vertex
        back_payload = {"v": tgt_vals}
        if tagged:
            back_payload["tag"] = deliv.payload["tag"]
        back = msg.direct_send(
            ctx,
            deliv.payload["requester"],
            deliv.mask,
            back_payload,
            capacity=r,
            name=name + "/respond",
            wire_width=wire_width,
        )
        slot = back.payload["tag"] if tagged else back.dst_local
        with tracing.scope(tracing.EXCHANGE):
            out = jnp.zeros((r + 1, d), rv.dtype)
            out = out.at[jnp.where(back.mask, slot, r)].set(
                jnp.where(back.mask[:, None], back.payload["v"], 0),
                mode="drop"
            )[:r]
    overflow = deliv.overflow | back.overflow
    return (out[:, 0] if squeeze else out), overflow


@tracing.channel_call
def cm_propagate(
    ctx: ChannelContext,
    raw_edges,
    init,
    combiner_name: str,
    *,
    active0,
    update=None,
    max_iters: int = 100_000,
    name: str = "basic_propagation",
):
    """Baseline label propagation: one CombinedMessage superstep per
    iteration until global convergence (what the Propagation channel
    replaces). O(diameter) global iterations. Returns (labels, iters)."""
    from repro.core import combiners as cb

    comb = cb.get(combiner_name)
    n_loc, w = ctx.n_loc, ctx.num_workers
    upd = update or (lambda lab, inc, got: comb.fn(lab, inc))

    def body(carry):
        lab, active, _, it, nb, nm = carry
        tmp = ChannelContext(ctx.axis, w, n_loc)
        tmp.route_cap = ctx.route_cap
        valid = raw_edges.mask & active[raw_edges.src_local]
        vals = lab[raw_edges.src_local]
        if raw_edges.w is not None:
            pass  # weighted variants pass transform via update
        inc, got, _ = msg.combined_send(
            tmp, raw_edges.dst_global, valid, vals, comb,
            capacity=tmp.edge_capacity(n_loc), name="x",
        )
        new = upd(lab, inc, got)
        new_active = jnp.any(
            (new != lab).reshape(n_loc, -1), axis=-1
        )
        changed = jax.lax.psum(jnp.any(new_active).astype(jnp.int32), ctx.axis) > 0
        db = sum(jax.tree_util.tree_leaves(tmp.stats_bytes))
        dm = sum(jax.tree_util.tree_leaves(tmp.stats_msgs))
        return new, new_active, changed, it + 1, nb + db, nm + dm

    def cond(carry):
        _, _, changed, it, _, _ = carry
        return changed & (it < max_iters)

    z = jnp.asarray(0, TRAFFIC_DTYPE)
    init_c = (init, active0, jnp.asarray(True), jnp.asarray(0, jnp.int32), z, z)
    lab, _, _, iters, nb, nm = jax.lax.while_loop(cond, body, init_c)
    ctx.add_traffic(name, nb, nm)
    return lab, iters


def jump_component():
    """:func:`pj_converge` (request-respond flavor) as a composition-stack
    component — the full-jumping stage shared by the composed S-V and the
    typed-channel Boruvka (args ``(parents, mask)``, single stat key)."""
    from repro.core import compose

    def fn(ctx, name, parents, mask):
        return pj_converge(ctx, parents, mask, use_reqresp=True, name=name)

    return compose.Component(fn)


@tracing.channel_call
def pj_converge(ctx: ChannelContext, parents, mask, *, use_reqresp=True,
                max_iters: int = 64, name: str = "pj_loop",
                wire_width: int = None):
    """Pointer-jump `parents` to fixpoint (all point to their root).

    Runs inside a while_loop; traffic is accumulated into the carry and
    then credited to `ctx`. Returns (roots, iters).
    """
    n_loc, w = ctx.n_loc, ctx.num_workers
    me = ctx.me()

    def body(carry):
        p, _, it, nb, nm = carry
        tmp = ChannelContext(ctx.axis, w, n_loc)
        if use_reqresp:
            grand, _ = rr.request(ctx=tmp, dst=p, valid=mask,
                                  respond_vals=p, capacity=n_loc, name="x")
        else:
            grand, _ = direct_request_respond(tmp, p, mask, p, name="x",
                                              wire_width=wire_width)
        newp = jnp.where(mask, grand, p)
        changed = jax.lax.psum(jnp.any(newp != p).astype(jnp.int32), ctx.axis) > 0
        db = sum(jax.tree_util.tree_leaves(tmp.stats_bytes))
        dm = sum(jax.tree_util.tree_leaves(tmp.stats_msgs))
        return newp, changed, it + 1, nb + db, nm + dm

    def cond(carry):
        _, changed, it, _, _ = carry
        return changed & (it < max_iters)

    init = (parents, jnp.asarray(True), jnp.asarray(0, jnp.int32),
            jnp.asarray(0, TRAFFIC_DTYPE), jnp.asarray(0, TRAFFIC_DTYPE))
    p, _, iters, nb, nm = jax.lax.while_loop(cond, body, init)
    ctx.add_traffic(name, nb, nm)
    return p, iters
