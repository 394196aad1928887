"""Propagation channel (paper §IV-C3).

Label-propagation algorithms converge in O(diameter) Pregel supersteps.
This channel runs a *local fixpoint* over partition-internal edges between
global exchanges (the block-centric / async-GAS effect), so the number of
global rounds drops to roughly the diameter of the quotient graph over
partitions. Only values that changed since the last exchange are counted
as traffic (the dense buffer is static — the accounting reflects the
logical messages a sparse implementation would send, matching how the
paper counts).

The combiner h must be commutative+associative and the update monotone
(min/max-style) for the fixpoint to be order-insensitive — the same
requirement the paper places on h.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import combiners as cb
from repro.core.channel import TRAFFIC_DTYPE, ChannelContext
from repro.graph.pgraph import PropPlan
from repro.kernels import ops as kops
from repro.pregel import tracing


@tracing.channel_call
def propagate(
    ctx: ChannelContext,
    plan: PropPlan,
    init_vals: jax.Array,
    combiner,
    *,
    edge_transform: Optional[Callable] = None,
    update: Optional[Callable] = None,
    src_values: Optional[Callable] = None,
    max_inner: int = 10_000,
    max_outer: int = 10_000,
    name: str = "propagation",
):
    """Run propagation to global convergence.

    Args:
      init_vals: (n_loc,) or (n_loc, D) initial labels.
      combiner: h — combines incoming neighbor values into the vertex value.
      edge_transform: fn(per_edge_vals, edge_w) — f applied along an edge
        (e.g. `lambda v, w: v + w` for SSSP).
      update: fn(lab, incoming) -> new lab (default: combiner(lab, inc)).
      src_values: fn(lab) -> per-vertex value broadcast to out-neighbors
        (default: identity; used e.g. to mask frozen vertices).
    Returns:
      (labels, outer_rounds, inner_iters_total)
    """
    combiner = cb.get(combiner)
    squeeze = init_vals.ndim == 1
    lab0 = init_vals[:, None] if squeeze else init_vals
    d = lab0.shape[-1]
    dtype = lab0.dtype
    ident = combiner.ident_for(dtype)
    w, c = ctx.num_workers, plan.cut.slot_cap
    n_loc = ctx.n_loc
    me = ctx.me()
    upd = update or (lambda lab, inc: combiner.fn(lab, inc))
    srcv = src_values or (lambda lab: lab)

    def edge_vals(lab, src_idx, ew):
        pe = srcv(lab)[src_idx]
        if edge_transform is not None:
            pe = edge_transform(pe, ew)
        return pe

    def local_fixpoint(lab):
        def cond(carry):
            _, changed, it = carry
            return changed & (it < max_inner)

        def body(carry):
            lab, _, it = carry
            pe = edge_vals(lab, plan.int_src, plan.int_w)
            inc = kops.segment_combine(pe, plan.int_dst, n_loc, combiner,
                                       use_kernel=False)
            new = upd(lab, inc)
            return new, jnp.any(new != lab), it + 1

        lab, _, iters = jax.lax.while_loop(
            cond, body, (lab, jnp.asarray(True), jnp.asarray(0, jnp.int32))
        )
        return lab, iters

    # owner of each unique cut destination (derivable from the static plan)
    u_owner = jnp.where(
        plan.cut.pack_slot < w * c, plan.cut.pack_slot // c, w
    )  # (U,) int32, w = padding

    # mirrored cut plans (partition_graph(mirror_threshold=...)): the cut
    # edge_src table indexes an extended value space — local values
    # followed by every worker's exported-hub values, refreshed by one
    # all_gather per exchange (same contract as scatter_combine). Only
    # hubs whose value changed since the last exchange count as traffic,
    # matching the channel's changed-only accounting.
    hub_cap = plan.cut.hub_cap
    if hub_cap:
        exported = plan.cut.hub_local < n_loc  # (hub_cap,)
        hub_safe = jnp.minimum(plan.cut.hub_local, n_loc - 1)

    def cut_edge_vals(lab, prev_hub):
        base = srcv(lab)
        changed_h = jnp.asarray(0, TRAFFIC_DTYPE)
        mine = prev_hub
        if hub_cap:
            mine = jnp.where(exported[:, None], base[hub_safe], ident)
            with tracing.scope(tracing.EXCHANGE):
                hubs = jax.lax.all_gather(mine, ctx.axis)  # (W, hub_cap, D)
                base = jnp.concatenate([base, hubs.reshape(-1, d)], axis=0)
            changed_h = jnp.sum(
                jnp.any(mine != prev_hub, axis=-1) & exported
            ).astype(TRAFFIC_DTYPE)
        pe = base[plan.cut.edge_src]
        if edge_transform is not None:
            pe = edge_transform(pe, plan.cut.edge_w)
        return pe, mine, changed_h

    def outer_body(carry):
        lab, prev_u, prev_hub, rounds, it_total, nbytes, nmsgs, _ = carry
        lab, iters = local_fixpoint(lab)

        # cut exchange (scatter-combine over cut edges, changed-only traffic)
        pe, new_hub, changed_h = cut_edge_vals(lab, prev_hub)
        u_vals = kops.segment_combine(
            pe, plan.cut.edge_seg, plan.cut.u_cap, combiner,
            use_kernel=False, assume_sorted=True,
        )
        changed_u = jnp.any(u_vals != prev_u, axis=-1) & (u_owner != w)
        remote_changed = jnp.sum(changed_u & (u_owner != me)).astype(TRAFFIC_DTYPE)
        with tracing.scope(tracing.ROUTE):
            buf = jnp.full((w * c + 1, d), ident, dtype)
            buf = buf.at[plan.cut.pack_slot].set(u_vals, mode="drop")
            send = buf[: w * c].reshape(w, c, d)
        with tracing.scope(tracing.EXCHANGE):
            recv = jax.lax.all_to_all(send, ctx.axis, 0, 0, tiled=True)
        inc = kops.segment_combine(
            recv.reshape(w * c, d), plan.cut.recv_local.reshape(-1), n_loc,
            combiner, use_kernel=False,
        )
        new = upd(lab, inc)
        changed = jax.lax.psum(jnp.any(new != lab).astype(jnp.int32), ctx.axis) > 0
        width = d * jnp.dtype(dtype).itemsize
        delta = remote_changed + changed_h * (w - 1)
        return (
            new, u_vals, new_hub, rounds + 1, it_total + iters,
            nbytes + delta * width, nmsgs + delta, changed,
        )

    def outer_cond(carry):
        _, _, _, rounds, _, _, _, changed = carry
        return changed & (rounds < max_outer)

    prev0 = jnp.full((plan.cut.u_cap, d), ident, dtype)
    prev_hub0 = jnp.full((hub_cap, d), ident, dtype)
    init = (
        lab0, prev0, prev_hub0, jnp.asarray(0, jnp.int32),
        jnp.asarray(0, jnp.int32),
        jnp.asarray(0, TRAFFIC_DTYPE), jnp.asarray(0, TRAFFIC_DTYPE),
        jnp.asarray(True),
    )
    lab, _, _, rounds, iters, nbytes, nmsgs, _ = jax.lax.while_loop(
        outer_cond, outer_body, init
    )
    ctx.add_traffic(name, nbytes, nmsgs)
    return (lab[:, 0] if squeeze else lab), rounds, iters
