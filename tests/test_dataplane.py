"""The kernelized sparse data plane: one-pass bucket routing vs the
sort-route baseline (bit-identical ``Routed`` contract), the Pallas
bucket-rank kernel vs its jnp oracle, wire-message traffic accounting
(post-dedup, capacity-clamped), the density-adaptive exchange, the
batched union-frontier route pass vs Q per-lane passes (per-lane
``Routed`` contract, halted-lane masking, lane-varying-dst fallback),
and the ``use_kernel``/``route_impl``/``route_batch`` configuration
surface end to end (env var -> Engine knob -> RunResult)."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import strategies
from strategies import N_LOC, W, random_messages
from repro.core import compose
from repro.core import message as msg
from repro.core import routing
from repro.core.channel import ChannelContext
from repro.kernels import ops as kops
from repro.kernels import ref as kref

AXIS = "w"
MODES = ("host", "fused", "chunked")


def make_ctx():
    return ChannelContext(AXIS, W, N_LOC)


def run_sharded(fn, *args):
    return jax.vmap(fn, axis_name=AXIS)(*args)


def _route_fields(impl, dst, valid, payload, capacity):
    def shard(d, v, p):
        routed = routing.route(make_ctx(), d, v, p, capacity, impl=impl)
        return (routed.ids, routed.mask, routed.payload, routed.slot,
                routed.sent_count, routed.overflow)

    return run_sharded(shard, dst, valid, payload)


def _assert_bit_identical(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# bucket-route vs sort-route parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed,m,cap", [(0, 40, 40), (1, 64, 64), (2, 7, 7)])
def test_bucket_matches_sort_bit_identical(seed, m, cap):
    dst, valid, payload = random_messages(seed, m)
    _assert_bit_identical(
        _route_fields("bucket", dst, valid, payload, cap),
        _route_fields("sort", dst, valid, payload, cap),
    )


def test_bucket_matches_sort_edge_cases():
    m = 16
    zero_pay = {"x": jnp.zeros((W, m), jnp.float32)}
    # empty: no valid message anywhere
    dst = jnp.zeros((W, m), jnp.int32)
    none = jnp.zeros((W, m), bool)
    a = _route_fields("bucket", dst, none, zero_pay, m)
    b = _route_fields("sort", dst, none, zero_pay, m)
    _assert_bit_identical(a, b)
    assert not np.asarray(a[5]).any()          # no overflow
    assert int(np.asarray(a[4]).sum()) == 0    # no wire messages
    # all messages to one owner (vertex 0's worker), full valid
    all_valid = jnp.ones((W, m), bool)
    _assert_bit_identical(
        _route_fields("bucket", dst, all_valid, zero_pay, m),
        _route_fields("sort", dst, all_valid, zero_pay, m),
    )


def test_overflow_latch_equivalence_and_wire_clamp():
    """Capacity overflow: both impls latch the flag, and both charge only
    the messages that fit on the wire (capacity-clamped sent_count) —
    never the enqueued overflow."""
    m, cap = 16, 3
    dst = jnp.zeros((W, m), jnp.int32)  # everyone floods vertex 0
    valid = jnp.ones((W, m), bool)
    for impl in ("bucket", "sort"):
        ids, mask, _, slot, sent, ovf = _route_fields(
            impl, dst, valid, {}, cap)
        assert np.asarray(ovf).all(), impl
        np.testing.assert_array_equal(
            np.asarray(sent), np.tile(np.eye(W, dtype=np.int32)[0] * cap, (W, 1))
        )
        # exactly cap messages packed per worker, the rest dropped
        assert int((np.asarray(slot) < W * cap).sum()) == W * cap


def test_route_impl_env_and_scope(monkeypatch):
    monkeypatch.delenv("REPRO_ROUTE_IMPL", raising=False)
    assert routing.resolve_impl() == "bucket"
    monkeypatch.setenv("REPRO_ROUTE_IMPL", "sort")
    assert routing.resolve_impl() == "sort"
    with routing.impl_scope("bucket"):
        assert routing.resolve_impl() == "bucket"  # scope beats env
    assert routing.resolve_impl() == "sort"
    with pytest.raises(ValueError, match="unknown routing impl"):
        routing.resolve_impl("warp")


def test_route_batch_env_and_scope(monkeypatch):
    monkeypatch.delenv("REPRO_ROUTE_BATCH", raising=False)
    assert routing.resolve_batch() == "union"
    monkeypatch.setenv("REPRO_ROUTE_BATCH", "lane")
    assert routing.resolve_batch() == "lane"
    with routing.batch_scope("union"):
        assert routing.resolve_batch() == "union"  # scope beats env
    assert routing.resolve_batch() == "lane"
    assert routing.resolve_batch("union") == "union"  # explicit beats env
    with pytest.raises(ValueError, match="unknown route batch strategy"):
        routing.resolve_batch("fleet")


# ---------------------------------------------------------------------------
# batched routing: one union-frontier pass vs Q per-lane serial routes
# ---------------------------------------------------------------------------

NQ = 3


def _route_union_fields(dst, valid_l, payload_l, capacity, live):
    """Per-lane ``Routed`` views of the shared union-frontier pass.

    Reproduces the runtime's nesting: worker vmap (axis name) outside, a
    query vmap inside, with per-lane batched ``query_index``/``query_live``
    scalars on the context. ``dst`` (W, M) is lane-invariant; ``valid_l``
    and the payload leaves carry a (W, NQ, M, ...) lane axis."""
    nq = valid_l.shape[1]
    qidx = jnp.arange(nq, dtype=jnp.int32)
    live = jnp.asarray(live, bool)

    def shard(d, v, p):
        def lane(qi, vi, pi, lvi):
            ctx = ChannelContext(AXIS, W, N_LOC, query_index=qi,
                                 query_live=lvi, num_queries=nq)
            r = routing.route_union(ctx, d, vi, pi, capacity)
            return (r.ids, r.mask, r.payload, r.slot, r.sent_count,
                    r.overflow)

        return jax.vmap(lane)(qidx, v, p, live)

    return run_sharded(shard, dst, valid_l, payload_l)


def _serial_lane_fields(dst, valid_l, payload_l, capacity, live):
    """Q independent serial route passes — the reference the per-lane
    union views must reproduce (halted lanes route nothing)."""
    out = []
    for ql in range(valid_l.shape[1]):
        v = valid_l[:, ql] & bool(live[ql])
        p = jax.tree_util.tree_map(lambda a: a[:, ql], payload_l)
        out.append(_route_fields("bucket", dst, v, p, capacity))
    return out


def _block_rows(ids_c, mask_c, pay_slices):
    """Sorted (id, payload...) rows of one (receiver, sender) wire block —
    the union pass reorders slots within a block but must deliver exactly
    the serial multiset."""
    keep = np.asarray(mask_c)
    cols = [np.asarray(ids_c)[keep].reshape(-1, 1).astype(np.float64)]
    for leaf in pay_slices:
        a = np.asarray(leaf)[keep].astype(np.float64)
        cols.append(a.reshape(a.shape[0],
                              int(np.prod(a.shape[1:], dtype=np.int64))))
    mat = np.concatenate(cols, axis=1)
    return mat[np.lexsort(mat.T[::-1])]


def _assert_union_matches_serial(union, serial, capacity, dst):
    """The per-lane contract of the shared pass vs Q serial routes:

      - ``sent_count`` is exact (per-lane per-peer wire occupancy);
      - ``overflow`` is a conservative latch (union ranks dominate lane
        ranks): it never misses a serial overflow;
      - wherever the sending lane did not overflow, each (receiver,
        sender) block delivers the exact serial multiset of
        (id, payload) rows, and the sender-side slots place packed
        messages in the destination owner's block."""
    u_ids, u_mask, u_pay, u_slot, u_sent, u_ovf = union
    u_pay_leaves = jax.tree_util.tree_leaves(u_pay)
    nq = u_mask.shape[1]
    for ql in range(nq):
        s_ids, s_mask, s_pay, s_slot, s_sent, s_ovf = serial[ql]
        s_pay_leaves = jax.tree_util.tree_leaves(s_pay)
        np.testing.assert_array_equal(
            np.asarray(u_sent[:, ql]), np.asarray(s_sent))
        so = np.asarray(s_ovf)
        uo = np.asarray(u_ovf[:, ql])
        assert np.all(uo >= so), "union overflow missed a serial overflow"
        # sender-side slot contract: a packed slot lands in the block of
        # the destination's owner, and absent overflow the packed set is
        # exactly the serial one
        sl = np.asarray(u_slot[:, ql])
        packed = sl < W * capacity
        owner = np.clip(np.asarray(dst) // N_LOC, 0, W - 1)
        np.testing.assert_array_equal(
            (sl // capacity)[packed], owner[packed])
        for w in range(W):
            if not uo[w]:
                np.testing.assert_array_equal(
                    packed[w], np.asarray(s_slot[w]) < W * capacity)
        for wrecv in range(W):
            for wsend in range(W):
                if uo[wsend]:
                    continue  # drops differ under overflow; sets don't align
                got = _block_rows(
                    u_ids[wrecv, ql, wsend], u_mask[wrecv, ql, wsend],
                    [lf[wrecv, ql, wsend] for lf in u_pay_leaves])
                want = _block_rows(
                    s_ids[wrecv, wsend], s_mask[wrecv, wsend],
                    [lf[wrecv, wsend] for lf in s_pay_leaves])
                np.testing.assert_array_equal(got, want)


def _lane_instance(seed, m, nq=NQ, valid_frac=0.7):
    rng = np.random.default_rng(seed)
    dst = jnp.asarray(rng.integers(0, W * N_LOC, (W, m)).astype(np.int32))
    valid_l = jnp.asarray(rng.random((W, nq, m)) < valid_frac)
    payload_l = {
        "f": jnp.asarray(rng.normal(size=(W, nq, m)).astype(np.float32)),
        "i2": jnp.asarray(
            rng.integers(-9, 9, (W, nq, m, 2)).astype(np.int32)),
    }
    return dst, valid_l, payload_l


@pytest.mark.parametrize("case", ("plain", "overflow", "empty_lane",
                                  "halted_lane", "disjoint"))
def test_route_union_matches_per_lane(case):
    m = 24
    dst, valid_l, payload_l = _lane_instance(5, m)
    live = [True] * NQ
    cap = m
    if case == "overflow":
        cap = 3
    elif case == "empty_lane":
        valid_l = valid_l.at[:, 1].set(False)
    elif case == "halted_lane":
        live = [True, False, True]
    elif case == "disjoint":
        lane_of = jnp.arange(m) % NQ
        valid_l = valid_l & (lane_of[None, None, :] ==
                             jnp.arange(NQ)[None, :, None])
    union = _route_union_fields(dst, valid_l, payload_l, cap, live)
    serial = _serial_lane_fields(dst, valid_l, payload_l, cap, live)
    _assert_union_matches_serial(union, serial, cap, dst)


def test_route_union_halted_lane_cannot_pollute_the_wire():
    """The pad/halt fix: a halted lane's (stale, garbage) frontier must
    not reach the union — the live lanes' shared views are bit-identical
    to a run where that lane simply has nothing to send, and the halted
    lane's own view is empty."""
    m = 20
    dst, valid_l, payload_l = _lane_instance(9, m)
    stale = valid_l.at[:, 2].set(True)        # lane 2: full garbage frontier
    a = _route_union_fields(dst, stale, payload_l, m, [True, True, False])
    quiet = valid_l.at[:, 2].set(False)       # lane 2: genuinely empty
    b = _route_union_fields(dst, quiet, payload_l, m, [True, True, True])
    _assert_bit_identical(a, b)
    _, mask_a, _, _, sent_a, ovf_a = a
    assert int(np.asarray(sent_a)[:, 2].sum()) == 0
    assert not np.asarray(mask_a)[:, 2].any()
    assert not np.asarray(ovf_a)[:, 2].any()


def test_route_union_lane_varying_dst_falls_back_bit_identical():
    """A per-lane ``dst`` makes positional slot sharing unsound; the
    custom_vmap rule proves it via in_batched and runs Q serial passes —
    bit-identical to the per-lane reference, positions included."""
    m = 18
    rng = np.random.default_rng(13)
    dst_l = jnp.asarray(
        rng.integers(0, W * N_LOC, (W, NQ, m)).astype(np.int32))
    _, valid_l, payload_l = _lane_instance(13, m)
    nq = NQ
    qidx = jnp.arange(nq, dtype=jnp.int32)
    live = jnp.ones((nq,), bool)

    def shard(d, v, p):
        def lane(qi, di, vi, pi, lvi):
            ctx = ChannelContext(AXIS, W, N_LOC, query_index=qi,
                                 query_live=lvi, num_queries=nq)
            r = routing.route_union(ctx, di, vi, pi, m)
            return (r.ids, r.mask, r.payload, r.slot, r.sent_count,
                    r.overflow)

        return jax.vmap(lane)(qidx, d, v, p, live)

    union = run_sharded(shard, dst_l, valid_l, payload_l)
    for ql in range(nq):
        p = jax.tree_util.tree_map(lambda a: a[:, ql], payload_l)
        serial = _route_fields("bucket", dst_l[:, ql], valid_l[:, ql], p, m)
        _assert_bit_identical(
            jax.tree_util.tree_map(lambda a: a[:, ql], union), serial)


# ---------------------------------------------------------------------------
# hypothesis property tests (optional-import, PR 1 convention; shared
# instance space from tests/strategies.py)
# ---------------------------------------------------------------------------

if strategies.HAVE_HYPOTHESIS:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(
        seed=strategies.seeds,
        m=strategies.message_counts,
        cap_frac=st.floats(0.1, 1.0),
        valid_frac=strategies.fractions,
    )
    def test_route_parity_property(seed, m, cap_frac, valid_frac):
        """Random messages, random capacity (including overflowing ones):
        every Routed field is bit-identical across the two impls."""
        dst, valid, payload = random_messages(seed, m, valid_frac=valid_frac)
        cap = max(1, int(m * cap_frac))
        _assert_bit_identical(
            _route_fields("bucket", dst, valid, payload, cap),
            _route_fields("sort", dst, valid, payload, cap),
        )

    @settings(max_examples=15, deadline=None)
    @given(
        seed=strategies.seeds,
        m=st.integers(1, 48),
        cap_frac=st.floats(0.1, 1.0),
        valid_frac=strategies.fractions,
        live_bits=st.integers(0, 2 ** NQ - 1),
    )
    def test_route_union_parity_property(seed, m, cap_frac, valid_frac,
                                         live_bits):
        """Random lanes, random capacity (overflowing ones included),
        random halt pattern: every per-lane view of the union pass
        reproduces the serial per-lane contract — exact sent counts,
        conservative overflow, exact delivered multisets where the lane
        did not overflow, and empty views for halted lanes."""
        dst, valid_l, payload_l = _lane_instance(seed, m,
                                                 valid_frac=valid_frac)
        live = [bool((live_bits >> i) & 1) for i in range(NQ)]
        cap = max(1, int(m * cap_frac))
        union = _route_union_fields(dst, valid_l, payload_l, cap, live)
        serial = _serial_lane_fields(dst, valid_l, payload_l, cap, live)
        _assert_union_matches_serial(union, serial, cap, dst)

    @settings(max_examples=25, deadline=None)
    @given(seed=strategies.seeds, m=st.integers(1, 400),
           b=st.integers(1, 16))
    def test_bucket_ranks_kernel_property(seed, m, b):
        rng = np.random.default_rng(seed)
        keys = jnp.asarray(rng.integers(0, b + 1, m).astype(np.int32))
        rk, ck = kops.bucket_ranks(keys, b, use_kernel=True, block_msgs=64)
        rr, cr = kref.bucket_ranks_ref(keys, b)
        np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
        np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))


# ---------------------------------------------------------------------------
# bucket-rank kernel vs oracle (fixed cases; property sweep above)
# ---------------------------------------------------------------------------


def test_bucket_ranks_kernel_matches_ref():
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.integers(0, W + 1, 1000).astype(np.int32))
    rk, ck = kops.bucket_ranks(keys, W, use_kernel=True, block_msgs=128)
    rr, cr = kref.bucket_ranks_ref(keys, W)
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(rr))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))


def test_route_kernel_path_matches_reference():
    """route(impl='bucket') with the Pallas kernel (interpret) ==
    the jnp reference, under vmap like the real runtime."""
    dst, valid, payload = random_messages(7, 48)

    def shard(use_kernel):
        def fn(d, v, p):
            routed = routing.route(make_ctx(), d, v, p, 48,
                                   impl="bucket", use_kernel=use_kernel)
            return (routed.ids, routed.mask, routed.payload, routed.slot,
                    routed.sent_count, routed.overflow)
        return run_sharded(fn, dst, valid, payload)

    _assert_bit_identical(shard(True), shard(False))


# ---------------------------------------------------------------------------
# precomputed chunk plans (the ScatterPlan autotune path)
# ---------------------------------------------------------------------------


def test_plan_chunks_mirrors_kernel_padding():
    """ops.plan_chunks builds the host work list against the kernel's
    padded view; if the two paddings ever desynchronize the kernel
    combines the wrong chunks. Sweep block sizes that force several
    chunks per block, with the exact list and one padded past it."""
    rng = np.random.default_rng(21)
    n, e = 100, 1500
    seg_np = np.sort(rng.integers(0, n, e)).astype(np.int32)
    vals = jnp.asarray(rng.normal(size=(e, 2)).astype(np.float32))
    want = kref.segment_combine_ref(vals, jnp.asarray(seg_np), n, "sum")
    for br, be in [(8, 64), (32, 128), (128, 512)]:
        bounds = kops.plan_chunks(seg_np, n, br, be)
        blk, chunk = kops.build_work_list(*bounds)
        assert np.bincount(blk[chunk >= 0]).max() > 1
        for items in (len(blk), len(blk) + 5):
            got = kops.segment_combine(
                vals, jnp.asarray(seg_np), n, "sum", use_kernel=True,
                assume_sorted=True, block_rows=br, block_edges=be,
                work_list=kops.build_work_list(*bounds, items))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-5, atol=1e-5)


def test_scatter_plan_chunk_tables_drive_the_kernel():
    """The default-on-TPU path: segment_combine through a built
    ScatterPlan's work list == the reference, per worker; the plan
    reports the kernel's grid and its active items."""
    from repro.graph import generators as gen, pgraph

    g = gen.rmat(8, edge_factor=8, seed=7).symmetrized()
    pg = pgraph.partition_graph(g, W, "random", build=("scatter_out",))
    plan = pg.scatter_out
    nb = -(-plan.u_cap // plan.block_rows)
    ec = -(-plan.e_cap // plan.block_edges)
    assert plan.item_block.shape == (W, plan.grid_steps)
    assert plan.grid_steps <= nb + ec
    active = plan.active_items
    assert active.shape == (W,) and (active <= plan.grid_steps).all()
    rng = np.random.default_rng(8)
    for w in range(W):
        seg = plan.edge_seg[w]
        vals = jnp.asarray(rng.normal(size=(plan.e_cap, 1)).astype(np.float32))
        want = kref.segment_combine_ref(vals, seg, plan.u_cap, "min")
        got = kops.segment_combine(
            vals, seg, plan.u_cap, "min", use_kernel=True,
            assume_sorted=True, block_rows=plan.block_rows,
            block_edges=plan.block_edges,
            work_list=(plan.item_block[w], plan.item_chunk[w]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        _, chunk = kops.build_work_list(*kops.plan_chunks(
            np.asarray(seg), plan.u_cap, plan.block_rows, plan.block_edges))
        assert active[w] == (chunk >= 0).sum()


# ---------------------------------------------------------------------------
# traffic accounting: id bytes per wire message, post-dedup
# ---------------------------------------------------------------------------


def test_combined_send_charges_post_dedup_wire_messages():
    """Heavy duplication: the id bytes ride the deduped wire messages,
    not the enqueued sends."""
    rng = np.random.default_rng(11)
    m = 64
    dst = rng.integers(0, 8, (W, m)).astype(np.int32)  # few hot targets
    valid = rng.random((W, m)) < 0.8
    vals = rng.normal(size=(W, m)).astype(np.float32)

    def shard(d, v, x):
        ctx = make_ctx()
        msg.combined_send(ctx, d, v, x, "sum", capacity=m)
        return ctx.stats_msgs["combined_message"], ctx.stats_bytes["combined_message"]

    nm, nb = run_sharded(shard, jnp.asarray(dst), jnp.asarray(valid),
                         jnp.asarray(vals))
    for w in range(W):
        unique_remote = len({
            int(dst[w, i]) for i in range(m) if valid[w, i]
            and dst[w, i] // N_LOC != w
        })
        assert int(np.asarray(nm)[w]) == unique_remote
        assert int(np.asarray(nb)[w]) == unique_remote * (4 + 4)


@pytest.mark.slow
def test_composed_bytes_under_sums_equal_total():
    """Regression (accounting fix): per-component namespaced sums still
    reconstruct the run total exactly, on both routing impls."""
    from repro.algorithms import sv
    from repro.graph import generators as gen, pgraph

    g = gen.rmat(7, edge_factor=4, seed=3).symmetrized()
    pg = pgraph.partition_graph(
        g, W, "random", build=("scatter_out", "raw_out"))
    for impl in ("bucket", "sort"):
        with routing.impl_scope(impl):
            _, res = sv.run(pg, variant="composed")
        chan = sv.composed_channels()
        per_component = sum(
            res.bytes_under(f"sv/{key}") for key in chan.components)
        assert per_component == res.total_bytes
        per_msgs = sum(
            res.msgs_under(f"sv/{key}") for key in chan.components)
        assert per_msgs == res.total_msgs


# ---------------------------------------------------------------------------
# data plane on/off: mode parity and cross-impl bit-identity
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("impl", ("bucket", "sort"))
def test_mode_parity_with_dataplane_on_and_off(impl):
    """fused/chunked/host stay bit-identical (states, steps, stats) with
    the new data plane on (bucket) and off (sort) — and the two impls are
    bit-identical to each other."""
    from repro.algorithms import sv
    from repro.graph import generators as gen, pgraph

    g = gen.rmat(7, edge_factor=4, seed=5).symmetrized()
    pg = pgraph.partition_graph(
        g, W, "random", build=("scatter_out", "raw_out"))
    results = {}
    for mode in MODES:
        lab, res = sv.run(pg, variant="both", mode=mode, chunk_size=3,
                          route_impl=impl)
        results[mode] = (lab, res)
        assert res.route_impl == impl
    ref_lab, ref_res = results["host"]
    for mode in ("fused", "chunked"):
        lab, res = results[mode]
        np.testing.assert_array_equal(ref_lab, lab)
        assert res.steps == ref_res.steps
        assert res.bytes_by_channel == ref_res.bytes_by_channel
        assert res.msgs_by_channel == ref_res.msgs_by_channel
    # stash for the cross-impl comparison below
    _CROSS_IMPL[impl] = (ref_lab, ref_res.bytes_by_channel)


_CROSS_IMPL = {}


@pytest.mark.slow
def test_cross_impl_bit_identity():
    if {"bucket", "sort"} <= set(_CROSS_IMPL):
        lab_b, bytes_b = _CROSS_IMPL["bucket"]
        lab_s, bytes_s = _CROSS_IMPL["sort"]
        np.testing.assert_array_equal(lab_b, lab_s)
        assert bytes_b == bytes_s


# ---------------------------------------------------------------------------
# density-adaptive exchange
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("threshold,expect_dense,expect_sparse",
                         [(0.0, True, False), (1.1, False, True)])
def test_density_adaptive_combine_extremes(threshold, expect_dense,
                                           expect_sparse):
    """Forced thresholds on the wcc switch: only the chosen plane's
    traffic is accounted and labels never change."""
    from repro.algorithms import wcc
    from repro.graph import generators as gen, pgraph

    g = gen.rmat(7, edge_factor=4, seed=1).symmetrized()
    pg = pgraph.partition_graph(
        g, W, "random", build=("scatter_out", "raw_out"))
    lab_basic, _ = wcc.run(pg, variant="basic")
    lab, res = wcc.run(pg, variant="switch", dense_threshold=threshold)
    np.testing.assert_array_equal(lab_basic, lab)
    assert (res.bytes_under("wcc/dense") > 0) == expect_dense
    assert (res.bytes_under("wcc/sparse") > 0) == expect_sparse


# ---------------------------------------------------------------------------
# configuration surface: env var -> Engine knob -> RunResult
# ---------------------------------------------------------------------------


def test_use_kernel_env_and_scope(monkeypatch):
    monkeypatch.delenv("REPRO_USE_KERNEL", raising=False)
    assert kops.resolve_use_kernel() == (jax.default_backend() == "tpu")
    monkeypatch.setenv("REPRO_USE_KERNEL", "1")
    assert kops.resolve_use_kernel()
    monkeypatch.setenv("REPRO_USE_KERNEL", "off")
    assert not kops.resolve_use_kernel()
    with kops.use_kernel_scope(True):
        assert kops.resolve_use_kernel()     # scope beats env
        assert not kops.resolve_use_kernel(False)  # explicit beats scope


def test_engine_knobs_reach_run_result():
    from repro.algorithms import get_program
    from repro.graph import generators as gen, pgraph
    from repro.pregel.engine import Engine

    spec_g = gen.rmat(7, edge_factor=4, seed=0).symmetrized()
    pg = pgraph.partition_graph(spec_g, W, "random", build=("raw_out",))
    prog = get_program("wcc:basic")
    eng = Engine(route_impl="sort", use_kernel=False)
    res = eng.run(prog, pg)
    assert res.route_impl == "sort" and res.use_kernel is False
    # same engine, same graph: cached; a different data plane is a
    # different engine and a fresh compile
    eng2 = Engine(route_impl="bucket", use_kernel=False)
    res2 = eng2.run(prog, pg)
    assert res2.route_impl == "bucket"
    assert eng.compiles == 1 and eng2.compiles == 1
    np.testing.assert_array_equal(res.output, res2.output)
    assert res.bytes_by_channel == res2.bytes_by_channel
