"""PartitionedGraph — static-shape distributed graph with channel plans.

All routing decisions that the paper's system makes with per-message
hashing are precomputed here (host-side numpy) into dense, static-shape
plans. Arrays carry a leading ``W`` (worker) axis; the Pregel runtime maps
step functions over it with ``vmap`` (logical workers on one device) or
``shard_map`` (real mesh), and channels communicate via axis-name
collectives — identical code in both modes.

:func:`partition_graph` returns the plans as host arrays; :func:`place`
puts them on the devices that run them, once, each worker's rows on its
own device of a mesh.
"""
from __future__ import annotations

import concurrent.futures
import dataclasses
import os
import time
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.graph import partition as partition_lib
from repro.graph.generators import EdgeList
from repro.pregel import tracing
from repro.pregel.errors import PlanRangeError

INT32_MAX = 2**31 - 1
#: the mesh axis the workers lie along
AXIS = "workers"


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

    @property
    def window_steps(self) -> np.ndarray:
        """(W, 2) body work of each worker's segment-combine kernel call:
        the 8-row windows its narrow lane rows take, and its lane rows
        that take the block-wide sweep (``kops.window_steps``; host
        read-back)."""
        if self.item_chunk is None:
            return np.zeros((self.num_workers, 2), np.int64)
        from repro.kernels import ops as kops

        seg = np.asarray(self.edge_seg)
        blk, chunk = np.asarray(self.item_block), np.asarray(self.item_chunk)
        return np.array([kops.window_steps(
            seg[w], self.u_cap, self.block_rows, self.block_edges, blk[w],
            chunk[w]) for w in range(self.num_workers)], np.int64)


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
    # where place() put the graph and what it cost (None: host arrays, as
    # partition_graph returns them); host-only, scrubbed from every
    # compile-cache key like name and new_of_old
    placement: Optional["Placement"] = dataclasses.field(
        default=None, metadata=dict(static=True))

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


@dataclasses.dataclass(frozen=True)
class Placement:
    """What :func:`place` did: its host seconds, and the graph's bytes
    on each device it used, as ``(device, bytes)`` pairs."""

    seconds: float
    device_bytes: Tuple[Tuple[str, int], ...]


def worker_sharding(mesh, axis: str = AXIS) -> jax.sharding.NamedSharding:
    """Each worker's rows (the leading axis) on its own device of the
    mesh's ``axis``."""
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(axis))


def device_bytes(tree) -> Tuple[Tuple[str, int], ...]:
    """The bytes of ``tree``'s device arrays on each device."""
    total: Dict[str, int] = {}
    for leaf in jax.tree_util.tree_leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            key = str(shard.device)
            total[key] = total.get(key, 0) + shard.data.nbytes
    return tuple(sorted(total.items()))


def place(pg: PartitionedGraph, mesh=None) -> PartitionedGraph:
    """Put the graph where it runs, once: with a ``mesh``, each worker's
    rows of every plan on its own device of the mesh's ``workers`` axis,
    committed (:func:`worker_sharding`), so no device ever holds another
    worker's rows; with none, every array on the default device, as an
    ``Engine`` without a mesh places a host graph on its first run. Runs
    on the placed graph move no graph bytes. The result records the
    placement's seconds and bytes per device (:class:`Placement`)."""
    t0 = time.perf_counter()
    with tracing.span(tracing.PLACE):
        placed = jax.device_put(
            pg, None if mesh is None else worker_sharding(mesh))
        jax.block_until_ready(placed)
    return dataclasses.replace(placed, placement=Placement(
        time.perf_counter() - t0, device_bytes(placed)))


def check_placed(pg: PartitionedGraph, mesh, axis: str = AXIS) -> None:
    """Refuse a graph that is not placed by worker on ``mesh``: running
    it would move every misplaced array onto the mesh again on each
    call."""
    want = worker_sharding(mesh, axis)
    for path, leaf in jax.tree_util.tree_leaves_with_path(pg):
        # device arrays, or shapes placed for a compile without the chip
        have = getattr(leaf, "sharding", None)
        if have is not None and have.is_equivalent_to(want, len(leaf.shape)):
            continue
        where = "a host array" if have is None else f"on {have}"
        raise ValueError(
            f"graph array {jax.tree_util.keystr(path)} is {where}, not "
            f"placed by worker on this mesh ({dict(mesh.shape)}): call "
            "pgraph.place(pg, mesh) once and run the graph it returns")


def _each_worker(fn, n_workers: int) -> list:
    """``[fn(w) for w in range(n_workers)]``, in parallel threads: numpy
    releases the GIL in the array passes that fill a worker's rows, and
    each worker writes only its own."""
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=min(n_workers, os.cpu_count() or 1)) as pool:
        return list(pool.map(fn, range(n_workers)))


@dataclasses.dataclass
class _Runs:
    """Edges sorted by (home worker, destination, source index), ties in
    edge order: worker ``w``'s run is ``keys[cuts[w]:cuts[w + 1]]``, each
    key ``(w * n_pad + dst) * span + src_idx``. ``src_idx`` is the index
    an edge gathers its source value from: the owner-local source id, or
    a mirror slot at or past ``n_loc``."""

    keys: np.ndarray
    cuts: np.ndarray
    weights: Optional[np.ndarray]
    span: int
    n_pad: int

    @classmethod
    def sort(cls, keys, weights, n_workers: int, n_pad: int,
             span: int) -> "_Runs":
        """Sort ``keys`` (a fresh array, sorted in place)."""
        if weights is None:
            keys.sort()  # equal keys are equal edges: no order to keep
        else:
            order = np.argsort(keys, kind="stable")
            keys, weights = keys[order], weights[order]
        cuts = np.searchsorted(keys, np.arange(n_workers + 1)
                               * (n_pad * span))
        return cls(keys, cuts, weights, span, n_pad)

    def run(self, w: int, lo: int = 0, hi: Optional[int] = None):
        """Keys and weights of worker ``w``'s run, or of its slice."""
        a = self.cuts[w]
        b = self.cuts[w + 1] if hi is None else a + hi
        wt = None if self.weights is None else self.weights[a + lo:b]
        return self.keys[a + lo:b], wt

    def split(self, w: int):
        """(src_idx, dst, weights) of worker ``w``'s run."""
        key, wt = self.run(w)
        d, s = np.divmod(key, self.span)
        d -= w * self.n_pad
        return s, d, wt


def _key_range(n_workers: int, n_pad: int, span: int) -> None:
    if n_workers * n_pad * span > np.iinfo(np.int64).max:
        raise PlanRangeError(
            f"plan sort key (W * n_pad * {span}) exceeds the int64 range",
            channels=("partition",))


def _owner_runs(src, dst, weights, n_workers: int, n_loc: int) -> _Runs:
    """Every edge homed at its source's owner."""
    n_pad = n_workers * n_loc
    _key_range(n_workers, n_pad, n_loc)
    # (home * n_pad + dst) * n_loc + (src - home * n_loc), in place
    keys = src // n_loc
    keys *= n_pad * n_loc - n_loc
    keys += dst * n_loc
    keys += src
    return _Runs.sort(keys, weights, n_workers, n_pad, n_loc)


def _mirrored_runs(src, dst, weights, n_workers: int, n_loc: int,
                   align: int, threshold: int):
    """Hub mirroring: a cut edge whose source degree (in this plan's
    orientation) exceeds the threshold is re-homed to the *destination*
    owner — the mirror combines it locally, so the hub's fan-out costs
    one broadcast slot per worker instead of one wire entry per unique
    remote destination; it gathers its source value from a mirror slot.
    Returns (runs, hub_cap, hub_local, mirrored edges), or None where no
    edge is mirrored."""
    W = n_workers
    n_pad = W * n_loc
    if not len(src):
        return None
    owner_src = src // n_loc
    owner_dst = dst // n_loc
    deg_src = np.bincount(src, minlength=n_pad)
    mir = (deg_src[src] > threshold) & (owner_src != owner_dst)
    if not mir.any():
        return None
    hub_ids = np.unique(src[mir])  # sorted => grouped by owner
    hub_owner = hub_ids // n_loc
    per_owner = np.bincount(hub_owner, minlength=W)
    hub_cap = _bucket_cap(int(per_owner.max(initial=0)), align)
    starts = np.concatenate([[0], np.cumsum(per_owner)])[:-1]
    rank_of = np.zeros(n_pad, np.int64)
    rank_of[hub_ids] = np.arange(len(hub_ids)) - starts[hub_owner]
    hub_local = np.full((W, hub_cap), n_loc, np.int32)
    for w in range(W):
        mine = hub_ids[hub_owner == w]
        hub_local[w, : len(mine)] = (mine - w * n_loc).astype(np.int32)
    home = np.where(mir, owner_dst, owner_src)
    src_idx = np.where(mir, n_loc + owner_src * hub_cap + rank_of[src],
                       src - owner_src * n_loc)
    span = n_loc + W * hub_cap
    _key_range(W, n_pad, span)
    runs = _Runs.sort((home * n_pad + dst) * span + src_idx, weights, W,
                      n_pad, span)
    return runs, hub_cap, hub_local, int(mir.sum())


def _build_scatter_plan(
    runs: _Runs,
    n_workers: int,
    n_loc: int,
    align: int = 8,
    hub_cap: int = 0,
    hub_local: Optional[np.ndarray] = None,
    mirrored: int = 0,
) -> ScatterPlan:
    """The plan from each worker's edges sorted by destination: a
    worker's unique destinations (sorted, so grouped by owner) fall out
    of its run's boundaries."""
    W = n_workers
    weighted = runs.weights is not None
    lengths = np.diff(runs.cuts)
    e_cap = _bucket_cap(int(lengths.max(initial=0)), align)
    edge_src = np.zeros((W, e_cap), np.int32)
    edge_seg = np.zeros((W, e_cap), np.int32)
    edge_w = np.zeros((W, e_cap), np.float32) if weighted else None

    def edge_rows(w):
        s, d, wt = runs.split(w)
        e = len(s)
        edge_src[w, :e] = s
        first = np.empty(e, bool)
        first[:1] = True
        np.not_equal(d[1:], d[:-1], out=first[1:])
        np.cumsum(first, dtype=np.int32, out=edge_seg[w, :e])
        edge_seg[w, :e] -= 1
        if weighted:
            edge_w[w, :e] = wt
        u = d[first]
        return u, np.bincount(u // n_loc, minlength=W)

    unique = _each_worker(edge_rows, W)
    u_cap = _bucket_cap(max(len(u) for u, _ in unique), align)
    c = _bucket_cap(int(max(cnt.max(initial=0) for _, cnt in unique)),
                    align)
    _check_int32_extent("scatter_plan/pack_slot (W * slot_cap)", W * c)
    _check_int32_extent(
        "scatter_plan/edge_src (n_loc + W * hub_cap)",
        n_loc + W * hub_cap)

    pack_slot = np.full((W, u_cap), W * c, np.int32)
    recv_local = np.full((W, W, c), n_loc, np.int32)
    send_count = np.zeros((W, W), np.int32)
    remote = 0

    for w, (u, cnt) in enumerate(unique):
        k = len(u)
        edge_seg[w, lengths[w]:] = u_cap  # padding: dropped
        starts = np.concatenate([[0], np.cumsum(cnt)])[:-1]  # (W,)
        # u is sorted by global id => grouped by owner, contiguous
        owners_u = u // n_loc
        rank = np.arange(k) - starts[owners_u]
        pack_slot[w, :k] = (owners_u * c + rank).astype(np.int32)
        send_count[w] = cnt.astype(np.int32)
        remote += int(cnt.sum() - cnt[w])
        # receive side: peer w sends to owner p its u entries owned by p
        for p in range(W):
            mine = u[starts[p]:starts[p] + cnt[p]]
            recv_local[p, w, : len(mine)] = (mine - p * n_loc).astype(np.int32)

    # autotuned segment-combine block plan: block sizes chosen from the
    # edge distribution, per-worker work lists built against the kernel's
    # padded view (repro.kernels.ops.plan_chunks). Imported lazily: the
    # kernels package pulls in repro.core, which imports the channel
    # modules that import this one.
    from repro.kernels import ops as kops

    block_rows, block_edges = kops.autotune_block_sizes(u_cap, e_cap)
    bounds = _each_worker(lambda w: kops.plan_chunks(
        edge_seg[w], u_cap, block_rows, block_edges), W)
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
        edge_src=edge_src,
        edge_seg=edge_seg,
        edge_w=edge_w,
        pack_slot=pack_slot,
        recv_local=recv_local,
        send_count=send_count,
        item_block=np.stack([blk for blk, _ in work]),
        item_chunk=np.stack([chunk for _, chunk in work]),
        n_loc=n_loc,
        num_workers=W,
        e_cap=e_cap,
        u_cap=u_cap,
        slot_cap=c,
        remote_entries=remote,
        total_edges=int(lengths.sum()),
        block_rows=block_rows,
        block_edges=block_edges,
        hub_local=hub_local,
        hub_cap=hub_cap,
        mirrored_edges=mirrored,
    )


def _build_prop_plan(runs: _Runs, src_new, dst_new, weights, n_workers,
                     n_loc, align=8, mirror_threshold=None) -> PropPlan:
    """``runs`` are the edges homed at their source's owner: a worker's
    internal edges (both ends its own) are the slice of its run whose
    destination it owns, sorted by local destination then local source;
    the rest are its cut edges."""
    W = n_workers
    n_pad = runs.n_pad
    # where each run's own destinations begin and end
    inside = [np.searchsorted(runs.run(w)[0], (w * n_pad + w * n_loc
                                               + np.array([0, n_loc])) * n_loc)
              for w in range(W)]
    ei_cap = _bucket_cap(max(int(hi - lo) for lo, hi in inside), align)
    int_src = np.zeros((W, ei_cap), np.int32)
    int_dst = np.full((W, ei_cap), n_loc, np.int32)
    int_w = np.zeros((W, ei_cap), np.float32) if weights is not None else None

    def internal_rows(w):
        lo, hi = inside[w]
        key, wt = runs.run(w, lo, hi)
        d, s = np.divmod(key - (w * n_pad + w * n_loc) * n_loc, n_loc)
        int_src[w, : len(s)] = s
        int_dst[w, : len(d)] = d
        if int_w is not None and len(s):
            int_w[w, : len(s)] = wt

    _each_worker(internal_rows, W)

    hub = None
    if mirror_threshold is not None:
        cut = (src_new // n_loc) != (dst_new // n_loc)
        hub = _mirrored_runs(
            src_new[cut], dst_new[cut],
            weights[cut] if weights is not None else None,
            W, n_loc, align, mirror_threshold)
    if hub is None:
        # unmirrored, a worker's cut run is its run less the internal slice
        pieces = [runs.run(w, *span) for w in range(W)
                  for span in ((0, inside[w][0]), (inside[w][1], None))]
        keys = np.concatenate([k for k, _ in pieces])
        cut_w = (None if weights is None
                 else np.concatenate([wt for _, wt in pieces]))
        lengths = [len(k) for k, _ in pieces]
        cuts = np.concatenate([[0], np.cumsum(lengths)])[::2]
        hub = (_Runs(keys, cuts, cut_w, n_loc, n_pad),)
    return PropPlan(
        int_src=int_src,
        int_dst=int_dst,
        int_w=int_w,
        cut=_build_scatter_plan(hub[0], W, n_loc, align, *hub[1:]),
        ei_cap=ei_cap,
    )


def _build_raw_edges(src_new, dst_new, weights, n_workers, n_loc, align=8) -> RawEdges:
    W = n_workers
    owner = src_new // n_loc
    counts = np.bincount(owner, minlength=W)
    e_cap = _bucket_cap(int(counts.max(initial=0)), align)
    src_l = np.zeros((W, e_cap), np.int32)
    dst_g = np.zeros((W, e_cap), np.int32)
    ws = np.zeros((W, e_cap), np.float32) if weights is not None else None
    mask = np.zeros((W, e_cap), bool)

    def rows(w):
        sel = owner == w
        e = counts[w]
        src_l[w, :e] = src_new[sel] - w * n_loc
        dst_g[w, :e] = dst_new[sel]
        if ws is not None and e:
            ws[w, :e] = weights[sel]
        mask[w, :e] = True

    _each_worker(rows, W)
    return RawEdges(
        src_local=src_l,
        dst_global=dst_g,
        w=ws,
        mask=mask,
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
        if e.min() < 0 or e.max() >= g.n:
            bad = (e < 0) | (e >= g.n)
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
    # one flag per (sending worker, destination): a set without a sort
    seen = np.zeros(n_workers * n_pad, bool)
    seen[(src // n_loc) * n_pad + dst] = True
    return int(seen.reshape(n_workers * n_workers, n_loc).sum(axis=1).max())


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
    """Partition + relabel a graph and precompute the requested plans, as
    host arrays (:func:`place` puts them on devices).

    build: subset of {"scatter_out", "scatter_in", "prop_out", "prop_in",
    "raw_out", "raw_in"}.

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
    deg = np.bincount(src, minlength=W * n_loc).astype(np.int32)

    # each orientation's edges sorted once, homed at their source's owner:
    # the unmirrored scatter plans and every prop plan read them
    owner_runs = {}

    def runs_of(a, b, key):
        if key not in owner_runs:
            owner_runs[key] = _owner_runs(a, b, w, W, n_loc)
        return owner_runs[key]

    def scatter(a, b, key):
        hub = (None if thr is None
               else _mirrored_runs(a, b, w, W, n_loc, align, thr))
        if hub is None:
            hub = (runs_of(a, b, key),)
        return _build_scatter_plan(hub[0], W, n_loc, align, *hub[1:])

    def prop(a, b, key):
        return _build_prop_plan(runs_of(a, b, key), a, b, w, W, n_loc,
                                align, mirror_threshold=thr)

    plans = {}
    if "scatter_out" in build:
        plans["scatter_out"] = scatter(src, dst, "out")
    if "scatter_in" in build:
        plans["scatter_in"] = scatter(dst, src, "in")
    if "prop_out" in build:
        plans["prop_out"] = prop(src, dst, "out")
    if "prop_in" in build:
        plans["prop_in"] = prop(dst, src, "in")
    owner_runs.clear()
    if "raw_out" in build:
        plans["raw_out"] = _build_raw_edges(src, dst, w, W, n_loc, align)
    if "raw_in" in build:
        plans["raw_in"] = _build_raw_edges(dst, src, w, W, n_loc, align)

    def route_bound(a, b, plan):
        # an unmirrored scatter plan's send counts are this very bound
        if plan is not None and not plan.mirrored_edges:
            return int(plan.send_count.max())
        return _route_cap_bound(a, b, W, n_loc)

    route_cap = max(route_bound(src, dst, plans.get("scatter_out")),
                    route_bound(dst, src, plans.get("scatter_in")))
    route_cap = _bucket_cap(route_cap, align) if route_cap else 0

    return PartitionedGraph(
        v_mask=v_mask,
        deg_out=deg.reshape(W, n_loc),
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
