"""PartitionedGraph — static-shape distributed graph with channel plans.

All routing decisions that the paper's system makes with per-message
hashing are precomputed here (host-side numpy) into dense, static-shape
plans. Arrays carry a leading ``W`` (worker) axis; the Pregel runtime maps
step functions over it with ``vmap`` (logical workers on one device) or
``shard_map`` (real mesh), and channels communicate via axis-name
collectives — identical code in both modes.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph import partition as partition_lib
from repro.graph.generators import EdgeList
from repro.pregel.errors import PlanRangeError

INT32_MAX = 2**31 - 1


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _check_int32_extent(what: str, value: int) -> None:
    """Plan tables and wire slots are int32; any extent past 2**31 - 1
    would silently wrap into another worker's range and corrupt routes.
    Validated at plan-build/trace time (extents are pure functions of the
    static caps) so the failure is structured, not a wrong answer."""
    if value > INT32_MAX:
        raise PlanRangeError(
            f"{what} = {value} exceeds the int32 range ({INT32_MAX}); "
            "the wire-slot ids (owner * C + rank) and plan tables would "
            "wrap. Reduce workers x capacity (or shrink the graph/caps).",
            channels=(what,),
        )


def _bucket_cap(x: int, align: int) -> int:
    """Slot caps are bucketed to the next power of two (floored at
    ``align``): every static cap enters the compiled loop's shape
    signature, so same-topology graphs whose raw per-worker counts differ
    slightly land on identical caps and share one Engine compile."""
    x = max(x, 1)
    return max(align, 1 << (x - 1).bit_length())


class HostArray:
    """Host-side numpy array kept OUT of the jax pytree (static aux data
    with identity hashing — it never changes after construction)."""

    def __init__(self, arr):
        self.arr = np.asarray(arr)

    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return other is self


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class ScatterPlan:
    """Static routing plan for the scatter-combine pattern.

    Per worker: local edges sorted by destination, sender-side dedup to one
    entry per unique destination, positional slots into the all_to_all
    buffer (no vertex ids on the wire), and the receive-side local indices.
    """

    edge_src: jax.Array      # (W, E_cap) i32 local src idx (pad 0, masked by seg)
    edge_seg: jax.Array      # (W, E_cap) i32 unique-dst index (pad U_cap: dropped)
    edge_w: Optional[jax.Array]  # (W, E_cap) f32 edge weights or None
    pack_slot: jax.Array     # (W, U_cap) i32 slot in (W*C) send buf (pad W*C)
    recv_local: jax.Array    # (W, W, C) i32 local dst idx (pad n_loc)
    send_count: jax.Array    # (W, W) i32 real entries per peer
    # autotuned segment-combine kernel plan (host-built from the edge
    # distribution; the statics ride the treedef, so the block choice is
    # part of every compile-cache key that includes this plan): the
    # kernel's flat work list, one (row block, chunk) item per grid step
    # (repro.kernels.ops.build_work_list)
    item_block: Optional[jax.Array]  # (W, T) i32 row block per item
    item_chunk: Optional[jax.Array]  # (W, T) i32 chunk per item (~c: none)
    # static metadata
    n_loc: int = dataclasses.field(metadata=dict(static=True))
    num_workers: int = dataclasses.field(metadata=dict(static=True))
    e_cap: int = dataclasses.field(metadata=dict(static=True))
    u_cap: int = dataclasses.field(metadata=dict(static=True))
    slot_cap: int = dataclasses.field(metadata=dict(static=True))
    remote_entries: int = dataclasses.field(metadata=dict(static=True))
    total_edges: int = dataclasses.field(metadata=dict(static=True))
    block_rows: int = dataclasses.field(default=0, metadata=dict(static=True))
    block_edges: int = dataclasses.field(default=0, metadata=dict(static=True))
    # hub mirroring (partition_graph(mirror_threshold=...)): cut edges
    # whose source degree exceeds the threshold are *re-homed* to the
    # destination owner and combined there (mirror-side pre-combine). The
    # mirror reads the hub's value from an extended gather index
    # ``n_loc + owner(hub) * hub_cap + hub_rank`` — the per-superstep
    # mirror->master refresh is a static all_gather of each owner's
    # exported-hub table (see repro.core.scatter_combine).
    hub_local: Optional[jax.Array] = None  # (W, hub_cap) i32 owner-local
    #                                        idx of exported hubs (pad n_loc)
    hub_cap: int = dataclasses.field(default=0, metadata=dict(static=True))
    mirrored_edges: int = dataclasses.field(
        default=0, metadata=dict(static=True))

    @property
    def grid_steps(self) -> int:
        """Grid steps of one worker's segment-combine kernel call (static:
        the work list's length)."""
        return 0 if self.item_block is None else self.item_block.shape[-1]

    @property
    def active_items(self) -> np.ndarray:
        """(W,) work items per worker that combine a chunk; the rest of
        :attr:`grid_steps` only set empty blocks or pad (host read-back)."""
        if self.item_chunk is None:
            return np.zeros(self.num_workers, np.int64)
        return (np.asarray(self.item_chunk) >= 0).sum(axis=-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RawEdges:
    """Unsorted per-worker edge lists (src local) — what the *baseline*
    message channels iterate over each superstep (no preprocessing)."""

    src_local: jax.Array   # (W, E_cap) i32
    dst_global: jax.Array  # (W, E_cap) i32
    w: Optional[jax.Array]  # (W, E_cap) f32
    mask: jax.Array        # (W, E_cap) bool
    e_cap: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PropPlan:
    """Plan for the propagation channel: partition-internal CSR (for the
    local fixpoint) + a ScatterPlan over cut edges (for global exchange)."""

    int_src: jax.Array       # (W, Ei_cap) i32 local src idx
    int_dst: jax.Array       # (W, Ei_cap) i32 local dst idx, sorted (pad n_loc)
    int_w: Optional[jax.Array]   # (W, Ei_cap) f32
    cut: ScatterPlan
    ei_cap: int = dataclasses.field(metadata=dict(static=True))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PartitionedGraph:
    v_mask: jax.Array        # (W, n_loc) bool
    deg_out: jax.Array       # (W, n_loc) i32
    scatter_out: Optional[ScatterPlan]
    scatter_in: Optional[ScatterPlan]
    prop_out: Optional[PropPlan]
    prop_in: Optional[PropPlan]
    raw_out: Optional[RawEdges]
    raw_in: Optional[RawEdges]
    n: int = dataclasses.field(metadata=dict(static=True))
    num_workers: int = dataclasses.field(metadata=dict(static=True))
    n_loc: int = dataclasses.field(metadata=dict(static=True))
    directed: bool = dataclasses.field(metadata=dict(static=True))
    name: str = dataclasses.field(metadata=dict(static=True))
    new_of_old: HostArray = dataclasses.field(metadata=dict(static=True))
    # partition-derived per-peer capacity bound for *edge-derived* routed
    # sends (max over (home worker, owner) pairs of unique destinations,
    # both orientations, pow2-bucketed; 0 = unknown). Deduping routed
    # channels can size their per-owner all_to_all buffers with this
    # instead of the full-width n_loc — see ChannelContext.edge_capacity.
    # A static field, so it rides the treedef into graph_signature and
    # every Engine compile-cache key.
    route_cap: int = dataclasses.field(default=0, metadata=dict(static=True))

    @property
    def n_pad(self) -> int:
        return self.num_workers * self.n_loc

    def to_local(self, per_vertex_np):
        """(n,) old-id host array -> (W, n_loc) device array in new-id space."""
        arr = np.asarray(per_vertex_np)
        out_shape = (self.n_pad,) + arr.shape[1:]
        out = np.zeros(out_shape, dtype=arr.dtype)
        out[self.new_of_old.arr] = arr
        return jnp.asarray(out.reshape((self.num_workers, self.n_loc) + arr.shape[1:]))

    def to_global(self, per_local):
        """(W, n_loc, ...) device array -> (n,) host array in old-id space."""
        flat = np.asarray(per_local).reshape((self.n_pad,) + per_local.shape[2:])
        return flat[self.new_of_old.arr]

    def global_ids(self):
        """(W, n_loc) the new-space global id of every slot."""
        return (
            jnp.arange(self.num_workers, dtype=jnp.int32)[:, None] * self.n_loc
            + jnp.arange(self.n_loc, dtype=jnp.int32)[None, :]
        )


def _build_scatter_plan(
    src_new: np.ndarray,
    dst_new: np.ndarray,
    weights: Optional[np.ndarray],
    n_workers: int,
    n_loc: int,
    align: int = 8,
    mirror_threshold: Optional[int] = None,
) -> ScatterPlan:
    W = n_workers
    n_pad = W * n_loc
    owner_src = src_new // n_loc
    owner_dst = dst_new // n_loc

    # hub mirroring: a cut edge whose source degree (in this plan's
    # orientation) exceeds the threshold is re-homed to the *destination*
    # owner — the mirror combines it locally, so the hub's fan-out costs
    # one broadcast slot per worker instead of one wire entry per unique
    # remote destination. src_idx below is the (possibly extended) gather
    # index each edge reads its source value from.
    home = owner_src
    src_idx = src_new - owner_src * n_loc
    hub_cap = 0
    mirrored = 0
    hub_local_np = None
    if mirror_threshold is not None and len(src_new):
        deg_src = np.bincount(src_new, minlength=n_pad)
        mir = (deg_src[src_new] > mirror_threshold) & (owner_src != owner_dst)
        if mir.any():
            hub_ids = np.unique(src_new[mir])  # sorted => grouped by owner
            hub_owner = hub_ids // n_loc
            per_owner = np.bincount(hub_owner, minlength=W)
            hub_cap = _bucket_cap(int(per_owner.max(initial=0)), align)
            starts = np.concatenate([[0], np.cumsum(per_owner)])[:-1]
            rank_of = np.zeros(n_pad, np.int64)
            rank_of[hub_ids] = np.arange(len(hub_ids)) - starts[hub_owner]
            hub_local_np = np.full((W, hub_cap), n_loc, np.int32)
            for w in range(W):
                mine = hub_ids[hub_owner == w]
                hub_local_np[w, : len(mine)] = (mine - w * n_loc).astype(
                    np.int32)
            home = np.where(mir, owner_dst, owner_src)
            src_idx = np.where(
                mir, n_loc + owner_src * hub_cap + rank_of[src_new], src_idx)
            mirrored = int(mir.sum())

    e_caps, u_caps, c_caps = [], [], []
    per_worker = []
    for w in range(W):
        sel = home == w
        s, d = src_idx[sel], dst_new[sel]
        wt = weights[sel] if weights is not None else None
        order = np.lexsort((s, d))
        s, d = s[order], d[order]
        wt = wt[order] if wt is not None else None
        u, seg = np.unique(d, return_inverse=True) if len(d) else (
            np.zeros(0, np.int64), np.zeros(0, np.int64))
        owners_u = u // n_loc
        cnt = np.bincount(owners_u, minlength=W)
        per_worker.append((s, d, wt, u, seg, owners_u, cnt))
        e_caps.append(len(s))
        u_caps.append(len(u))
        c_caps.append(cnt.max(initial=0))

    e_cap = _bucket_cap(max(e_caps), align)
    u_cap = _bucket_cap(max(u_caps), align)
    c = _bucket_cap(int(max(c_caps)), align)
    _check_int32_extent("scatter_plan/pack_slot (W * slot_cap)", W * c)
    _check_int32_extent(
        "scatter_plan/edge_src (n_loc + W * hub_cap)",
        n_loc + W * hub_cap)

    edge_src = np.zeros((W, e_cap), np.int32)
    edge_seg = np.full((W, e_cap), u_cap, np.int32)
    edge_w = np.zeros((W, e_cap), np.float32) if weights is not None else None
    pack_slot = np.full((W, u_cap), W * c, np.int32)
    recv_local = np.full((W, W, c), n_loc, np.int32)
    send_count = np.zeros((W, W), np.int32)
    remote = 0
    total = 0

    for w in range(W):
        s, d, wt, u, seg, owners_u, cnt = per_worker[w]
        k, e = len(u), len(s)
        total += e
        edge_src[w, :e] = s.astype(np.int32)
        edge_seg[w, :e] = seg.astype(np.int32)
        if edge_w is not None and e:
            edge_w[w, :e] = wt
        starts = np.concatenate([[0], np.cumsum(cnt)])[:-1]  # (W,)
        # u is sorted by global id => grouped by owner, contiguous
        rank = np.arange(k) - starts[owners_u]
        pack_slot[w, :k] = (owners_u * c + rank).astype(np.int32)
        send_count[w] = cnt.astype(np.int32)
        remote += int(cnt.sum() - cnt[w])
        # receive side: peer w sends to owner p its u entries owned by p
        for p in range(W):
            mine = u[owners_u == p]
            recv_local[p, w, : len(mine)] = (mine - p * n_loc).astype(np.int32)

    # autotuned segment-combine block plan: block sizes chosen from the
    # edge distribution, per-worker work lists built against the kernel's
    # padded view (repro.kernels.ops.plan_chunks). Imported lazily: the
    # kernels package pulls in repro.core, which imports the channel
    # modules that import this one.
    from repro.kernels import ops as kops

    block_rows, block_edges = kops.autotune_block_sizes(u_cap, e_cap)
    bounds = [kops.plan_chunks(edge_seg[w], u_cap, block_rows, block_edges)
              for w in range(W)]
    # The work list's length T is the kernel's grid, a static shape set
    # by the edge *skew*, not the caps: the heaviest worker's item count,
    # bucketed to the next power of two (capped at the bound NB + EC) so
    # same-cap graphs with slightly different skew share a compile
    # signature. Lighter workers pad with items that combine nothing.
    start, _, ec = bounds[0]
    n_items = max(len(kops.build_work_list(*b)[0]) for b in bounds)
    n_items = min(_bucket_cap(n_items, 1), len(start) + ec)
    work = [kops.build_work_list(*b, n_items) for b in bounds]

    return ScatterPlan(
        edge_src=jnp.asarray(edge_src),
        edge_seg=jnp.asarray(edge_seg),
        edge_w=jnp.asarray(edge_w) if edge_w is not None else None,
        pack_slot=jnp.asarray(pack_slot),
        recv_local=jnp.asarray(recv_local),
        send_count=jnp.asarray(send_count),
        item_block=jnp.asarray(np.stack([blk for blk, _ in work])),
        item_chunk=jnp.asarray(np.stack([chunk for _, chunk in work])),
        n_loc=n_loc,
        num_workers=W,
        e_cap=e_cap,
        u_cap=u_cap,
        slot_cap=c,
        remote_entries=remote,
        total_edges=total,
        block_rows=block_rows,
        block_edges=block_edges,
        hub_local=(jnp.asarray(hub_local_np)
                   if hub_local_np is not None else None),
        hub_cap=hub_cap,
        mirrored_edges=mirrored,
    )


def _build_prop_plan(
    src_new, dst_new, weights, n_workers, n_loc, align=8,
    mirror_threshold=None,
) -> PropPlan:
    W = n_workers
    owner_s = src_new // n_loc
    owner_d = dst_new // n_loc
    internal = owner_s == owner_d
    cut = ~internal

    # internal CSR (per worker, sorted by local dst)
    ei = 0
    per_worker = []
    for w in range(W):
        sel = internal & (owner_s == w)
        s = (src_new[sel] - w * n_loc).astype(np.int32)
        d = (dst_new[sel] - w * n_loc).astype(np.int32)
        wt = weights[sel] if weights is not None else None
        order = np.lexsort((s, d))
        per_worker.append((s[order], d[order], wt[order] if wt is not None else None))
        ei = max(ei, len(s))
    ei_cap = _bucket_cap(ei, align)
    int_src = np.zeros((W, ei_cap), np.int32)
    int_dst = np.full((W, ei_cap), n_loc, np.int32)
    int_w = np.zeros((W, ei_cap), np.float32) if weights is not None else None
    for w in range(W):
        s, d, wt = per_worker[w]
        int_src[w, : len(s)] = s
        int_dst[w, : len(d)] = d
        if int_w is not None and len(s):
            int_w[w, : len(s)] = wt

    cut_plan = _build_scatter_plan(
        src_new[cut], dst_new[cut],
        weights[cut] if weights is not None else None,
        n_workers, n_loc, align, mirror_threshold=mirror_threshold,
    )
    return PropPlan(
        int_src=jnp.asarray(int_src),
        int_dst=jnp.asarray(int_dst),
        int_w=jnp.asarray(int_w) if int_w is not None else None,
        cut=cut_plan,
        ei_cap=ei_cap,
    )


def _build_raw_edges(src_new, dst_new, weights, n_workers, n_loc, align=8) -> RawEdges:
    W = n_workers
    owner = src_new // n_loc
    counts = [int((owner == w).sum()) for w in range(W)]
    e_cap = _bucket_cap(max(counts, default=0), align)
    src_l = np.zeros((W, e_cap), np.int32)
    dst_g = np.zeros((W, e_cap), np.int32)
    ws = np.zeros((W, e_cap), np.float32) if weights is not None else None
    mask = np.zeros((W, e_cap), bool)
    for w in range(W):
        sel = owner == w
        e = int(sel.sum())
        src_l[w, :e] = (src_new[sel] - w * n_loc).astype(np.int32)
        dst_g[w, :e] = dst_new[sel].astype(np.int32)
        if ws is not None and e:
            ws[w, :e] = weights[sel]
        mask[w, :e] = True
    return RawEdges(
        src_local=jnp.asarray(src_l),
        dst_global=jnp.asarray(dst_g),
        w=jnp.asarray(ws) if ws is not None else None,
        mask=jnp.asarray(mask),
        e_cap=e_cap,
    )


def validate_edge_list(g) -> None:
    """Reject graphs whose edges index outside ``[0, n)`` or whose
    weights are NaN/inf, with the offending positions in the message."""
    if g.n < 1:
        raise ValueError(f"graph must have at least one vertex, got n={g.n}")
    e = np.asarray(g.edges)
    if e.size:
        if e.ndim != 2 or e.shape[1] != 2:
            raise ValueError(
                f"edges must be (E, 2) (src, dst), got shape {e.shape}")
        bad = (e < 0) | (e >= g.n)
        if bad.any():
            rows = np.flatnonzero(bad.any(axis=1))[:5]
            raise ValueError(
                f"{int(bad.any(axis=1).sum())} edge endpoint(s) outside "
                f"[0, {g.n}) — first bad edges at rows {rows.tolist()}: "
                f"{e[rows].tolist()}")
    if g.weights is not None:
        w = np.asarray(g.weights)
        if w.shape[0] != e.shape[0]:
            raise ValueError(
                f"weights length {w.shape[0]} != num edges {e.shape[0]}")
        nonfinite = ~np.isfinite(w)
        if nonfinite.any():
            rows = np.flatnonzero(nonfinite)[:5]
            raise ValueError(
                f"{int(nonfinite.sum())} non-finite edge weight(s) "
                f"(NaN/inf) — first at rows {rows.tolist()}: "
                f"{w[rows].tolist()}")


def _route_cap_bound(src, dst, n_workers: int, n_loc: int) -> int:
    """Max over (sending worker, owner) pairs of the number of *unique*
    destinations — the provable per-peer occupancy bound for any deduping
    routed send whose destinations are edge endpoints (any frontier's
    unique dsts per owner is a subset of the full edge set's)."""
    if not len(src):
        return 0
    n_pad = n_workers * n_loc
    key = (src // n_loc).astype(np.int64) * n_pad + dst
    u = np.unique(key)
    pair = (u // n_pad) * n_workers + (u % n_pad) // n_loc
    return int(np.bincount(pair, minlength=n_workers * n_workers).max())


def resolve_mirror_threshold(g: EdgeList, mirror_threshold) -> Optional[int]:
    """``None`` -> no mirroring; ``"auto"`` -> a degree several times the
    mean (hubs in the power-law sense); an int passes through."""
    if mirror_threshold is None:
        return None
    if mirror_threshold == "auto":
        avg = len(g.edges) / max(g.n, 1)
        return max(64, int(8 * avg))
    return int(mirror_threshold)


def partition_graph(
    g: EdgeList,
    n_workers: int,
    partitioner: str = "random",
    seed: int = 0,
    build=("scatter_out",),
    align: int = 8,
    mirror_threshold=None,
) -> PartitionedGraph:
    """Partition + relabel a graph and precompute the requested plans.

    build: subset of {"scatter_out", "scatter_in", "prop_out", "prop_in"}.

    mirror_threshold: enable hub mirroring in the scatter/prop-cut plans —
    ``None`` (off, plans identical to previous builds), an int degree
    threshold, or ``"auto"``. A vertex whose degree in a plan's
    orientation (counted over the edges that plan covers) exceeds the
    threshold gets a mirror slot on every worker its cut edges touch; the
    mirror pre-combines locally and the hub's value is refreshed by one
    static broadcast per superstep. Final vertex outputs are bit-identical
    to the unmirrored build for order-insensitive combiners (min/max/or —
    wcc, sv, sssp); floating-point ``sum`` may round differently (the
    reduction regroups), so leave mirroring off for e.g. pagerank if
    bit-stability matters.

    Rejects malformed inputs up front — an out-of-range endpoint or a
    non-finite weight would otherwise corrupt the relabel/scatter plans
    silently (numpy fancy indexing wraps negatives) and surface steps
    later as wrong answers, not errors.
    """
    validate_edge_list(g)
    if partitioner not in partition_lib.PARTITIONERS:
        raise ValueError(
            f"unknown partitioner {partitioner!r}; known partitioners: "
            f"{sorted(partition_lib.PARTITIONERS)}")
    new_of_old = partition_lib.PARTITIONERS[partitioner](g, n_workers, seed)
    n_loc = _round_up(-(-g.n // n_workers), align)
    src = new_of_old[g.edges[:, 0]]
    dst = new_of_old[g.edges[:, 1]]
    w = g.weights
    thr = resolve_mirror_threshold(g, mirror_threshold)

    W = n_workers
    _check_int32_extent("partition (W * n_loc)", W * n_loc)
    v_mask = np.zeros((W, n_loc), bool)
    flat = v_mask.reshape(-1)
    flat[np.asarray(new_of_old)] = True
    deg = np.zeros(W * n_loc, np.int32)
    np.add.at(deg, src, 1)

    plans = {}
    if "scatter_out" in build:
        plans["scatter_out"] = _build_scatter_plan(
            src, dst, w, W, n_loc, align, mirror_threshold=thr)
    if "scatter_in" in build:
        plans["scatter_in"] = _build_scatter_plan(
            dst, src, w, W, n_loc, align, mirror_threshold=thr)
    if "prop_out" in build:
        plans["prop_out"] = _build_prop_plan(
            src, dst, w, W, n_loc, align, mirror_threshold=thr)
    if "prop_in" in build:
        plans["prop_in"] = _build_prop_plan(
            dst, src, w, W, n_loc, align, mirror_threshold=thr)
    if "raw_out" in build:
        plans["raw_out"] = _build_raw_edges(src, dst, w, W, n_loc, align)
    if "raw_in" in build:
        plans["raw_in"] = _build_raw_edges(dst, src, w, W, n_loc, align)

    route_cap = max(_route_cap_bound(src, dst, W, n_loc),
                    _route_cap_bound(dst, src, W, n_loc))
    route_cap = _bucket_cap(route_cap, align) if route_cap else 0

    return PartitionedGraph(
        v_mask=jnp.asarray(v_mask),
        deg_out=jnp.asarray(deg.reshape(W, n_loc)),
        scatter_out=plans.get("scatter_out"),
        scatter_in=plans.get("scatter_in"),
        prop_out=plans.get("prop_out"),
        prop_in=plans.get("prop_in"),
        raw_out=plans.get("raw_out"),
        raw_in=plans.get("raw_in"),
        n=g.n,
        num_workers=W,
        n_loc=n_loc,
        directed=g.directed,
        name=g.name,
        new_of_old=HostArray(new_of_old),
        route_cap=route_cap,
    )
