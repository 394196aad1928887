"""Pallas segment_combine kernel vs the pure-jnp oracle: shape/dtype
sweeps + hypothesis property tests."""
import numpy as np
import jax.numpy as jnp
import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need hypothesis (requirements-dev.txt)"
)
from hypothesis import given, settings, strategies as st

from repro.core import combiners as cb
from repro.kernels import ops, ref
from repro.kernels import segment_combine as kseg

COMBINERS = ["sum", "min", "max"]


@pytest.mark.parametrize("combiner", COMBINERS)
@pytest.mark.parametrize(
    "e,n,d", [(64, 16, 1), (1000, 300, 1), (513, 128, 3), (2048, 777, 5),
              (4096, 64, 8), (100, 1000, 2)]
)
def test_kernel_matches_ref_f32(e, n, d, combiner):
    rng = np.random.default_rng(e + n + d)
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    vals = rng.normal(size=(e, d)).astype(np.float32)
    want = ref.segment_combine_ref(jnp.array(vals), jnp.array(seg), n, combiner)
    got = ops.segment_combine(
        jnp.array(vals), jnp.array(seg), n, combiner,
        use_kernel=True, assume_sorted=True,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("combiner", ["min", "max"])
def test_kernel_matches_ref_int32(combiner):
    rng = np.random.default_rng(0)
    seg = np.sort(rng.integers(0, 50, 400)).astype(np.int32)
    vals = rng.integers(-1000, 1000, (400, 2)).astype(np.int32)
    want = ref.segment_combine_ref(jnp.array(vals), jnp.array(seg), 50, combiner)
    got = ops.segment_combine(jnp.array(vals), jnp.array(seg), 50, combiner,
                              use_kernel=True, assume_sorted=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_kernel_unsorted_input_sorts():
    rng = np.random.default_rng(1)
    seg = rng.integers(0, 37, 300).astype(np.int32)
    vals = rng.normal(size=(300, 2)).astype(np.float32)
    want = ref.segment_combine_ref(jnp.array(vals), jnp.array(seg), 37, "sum")
    got = ops.segment_combine(jnp.array(vals), jnp.array(seg), 37, "sum",
                              use_kernel=True, assume_sorted=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_kernel_out_of_range_dropped():
    seg = np.array([0, 0, 1, 5, 9, 9], np.int32)  # 5, 9 out of range for n=4
    vals = np.ones((6, 1), np.float32)
    got = ops.segment_combine(jnp.array(vals), jnp.array(seg), 4, "sum",
                              use_kernel=True, assume_sorted=True)
    np.testing.assert_allclose(np.asarray(got)[:, 0], [2, 1, 0, 0])


def test_kernel_custom_block_sizes():
    rng = np.random.default_rng(2)
    seg = np.sort(rng.integers(0, 100, 1500)).astype(np.int32)
    vals = rng.normal(size=(1500, 2)).astype(np.float32)
    want = ref.segment_combine_ref(jnp.array(vals), jnp.array(seg), 100, "sum")
    for br, be in [(8, 64), (32, 128), (256, 1024)]:
        got = ops.segment_combine(jnp.array(vals), jnp.array(seg), 100, "sum",
                                  use_kernel=True, assume_sorted=True,
                                  block_rows=br, block_edges=be)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(
    e=st.integers(1, 600),
    n=st.integers(1, 200),
    combiner=st.sampled_from(COMBINERS),
    seed=st.integers(0, 2**31 - 1),
)
def test_kernel_property(e, n, combiner, seed):
    rng = np.random.default_rng(seed)
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    vals = rng.normal(size=(e, 1)).astype(np.float32)
    want = ref.segment_combine_ref(jnp.array(vals), jnp.array(seg), n, combiner)
    got = ops.segment_combine(jnp.array(vals), jnp.array(seg), n, combiner,
                              use_kernel=True, assume_sorted=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 50))
def test_min_by_first_combiner_property(seed, n):
    """min_by_first == argmin by key, payload carried along."""
    rng = np.random.default_rng(seed)
    e = 300
    seg = rng.integers(0, n, e).astype(np.int32)
    keys = rng.permutation(e).astype(np.float32)  # unique keys
    payload = rng.normal(size=(e, 2)).astype(np.float32)
    vals = np.concatenate([keys[:, None], payload], axis=1)
    got = cb.MIN_BY_FIRST.segment_reduce(jnp.array(vals), jnp.array(seg), n)
    got = np.asarray(got)
    for s in range(n):
        sel = seg == s
        if not sel.any():
            assert np.isinf(got[s, 0])
        else:
            i = np.flatnonzero(sel)[np.argmin(keys[sel])]
            np.testing.assert_allclose(got[s], vals[i], rtol=1e-6)


# ---------------------------------------------------------------------------
# lane-dense kernel bodies (the layouts the TPU lowering uses) vs kref,
# exact: lattice combiners, integer sums, and f32 sums of small integers
# (exact in any summation order)
# ---------------------------------------------------------------------------

SEG_CASES = {
    # id: (combiner, dtype, e, n, d, block_rows, block_edges)
    "int32-min": ("min", np.int32, 3000, 700, 1, 128, 1024),
    "int32-max": ("max", np.int32, 3000, 700, 1, 128, 1024),
    "f32-sum": ("sum", np.float32, 3000, 700, 1, 128, 512),
    "f32-min-d8": ("min", np.float32, 2500, 300, 8, 128, 256),
    "int32-sum-d3": ("sum", np.int32, 1000, 64, 3, 64, 128),
    "empty-segments": ("min", np.int32, 200, 5000, 1, 128, 1024),
    "one-row-block": ("max", np.int32, 100, 8, 2, 8, 128),
}


@pytest.mark.parametrize("case", sorted(SEG_CASES))
def test_segment_combine_kernel_exact(case):
    combiner, dtype, e, n, d, br, be = SEG_CASES[case]
    rng = np.random.default_rng(len(case))
    seg = np.sort(rng.integers(0, n, e)).astype(np.int32)
    vals = rng.integers(-50, 50, (e, d)).astype(dtype)
    want = ref.segment_combine_ref(jnp.array(vals), jnp.array(seg), n,
                                   combiner)
    got = ops.segment_combine(jnp.array(vals), jnp.array(seg), n, combiner,
                              use_kernel=True, assume_sorted=True,
                              block_rows=br, block_edges=be)
    if case == "empty-segments":
        assert (np.bincount(seg, minlength=n) == 0).sum() > n // 2
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_combine_kernel_rejects_unreducible_combiner():
    vals = jnp.ones((128, 1), jnp.float32)
    seg = jnp.zeros((128,), jnp.int32)
    with pytest.raises(ValueError, match="no reduction"):
        ops.segment_combine(vals, seg, 4, "prod", use_kernel=True,
                            assume_sorted=True)


def _skewed_segments(seed):
    """A Graph500-like hub: one segment holds 1500 edges (about 12 chunks
    of 128, so one row block of 8 covers many chunks), most row blocks
    hold none, and light blocks sit at both ends."""
    rng = np.random.default_rng(seed)
    seg = np.concatenate([rng.integers(0, 60, 200), np.full(1500, 1003),
                          rng.integers(1900, 2000, 300)])
    return np.sort(seg).astype(np.int32), 2000


@pytest.mark.parametrize("planned", [True, False], ids=["plan", "device"])
@pytest.mark.parametrize("combiner,dtype", [
    ("min", np.int32), ("max", np.int32), ("sum", np.int32),
    ("sum", np.float32)], ids=["int32-min", "int32-max", "int32-sum",
                               "f32-sum"])
def test_segment_combine_kernel_skewed_work_list(combiner, dtype, planned):
    """The work list on a skewed edge array equals the reference exactly
    (the f32 sum adds small integers), with the host plan's list and
    with the one built on the device."""
    seg, n = _skewed_segments(3)
    br, be = 8, 128
    rng = np.random.default_rng(4)
    vals = jnp.asarray(rng.integers(-50, 50, (len(seg), 2)).astype(dtype))
    work = None
    if planned:
        work = ops.build_work_list(*ops.plan_chunks(seg, n, br, be))
        blk, chunk = work
        assert np.bincount(blk[chunk >= 0]).max() >= 12  # the hub block
        assert (chunk < 0).sum() > len(blk) // 2  # mostly empty blocks
    got = ops.segment_combine(vals, jnp.asarray(seg), n, combiner,
                              use_kernel=True, assume_sorted=True,
                              block_rows=br, block_edges=be, work_list=work)
    want = ref.segment_combine_ref(vals, jnp.asarray(seg), n, combiner)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _lane_rows(br, case):
    """Sorted ids, one 128-edge lane row per list entry, built so that a
    named path of the kernel body runs; and the rows of output."""
    rng = np.random.default_rng(br)
    wide = kseg.WIDE_WINDOWS * kseg.WINDOW
    fill = lambda lo, hi: np.sort(np.r_[lo, hi - 1, rng.integers(lo, hi, 126)])
    n = 3 * br
    rows = {
        "one-window": [fill(16, 24), fill(24, 32)],
        "several-windows": [fill(8 + 3, 8 + wide - 4)],
        "wide": [np.arange(128) + br // 2 - 64],
        "straddle": [fill(br - 20, br + 20)],
        "all-padding": [fill(0, 8), np.full(128, n + 7)],
        "empty-block": [fill(0, 16), fill(2 * br, 2 * br + 16)],
        "last-window": [fill(br - 8, br), fill(n - 8, n)],
    }[case]
    return np.concatenate(rows).astype(np.int32), n


BODY_CASES = ["one-window", "several-windows", "wide", "straddle",
              "all-padding", "empty-block", "last-window"]


@pytest.mark.parametrize("br", [128, 256])
@pytest.mark.parametrize("case", BODY_CASES)
def test_segment_combine_kernel_body_paths(case, br):
    """Each path of the body (narrow lane rows in one or several windows,
    the block-wide sweep, a row across two blocks, a row of padding, an
    empty block, the block's last window) equals the reference exactly:
    integer min/max/sum and f32 sums of small integers, D = 1 and 2."""
    seg, n = _lane_rows(br, case)
    be = 256
    work = ops.build_work_list(*ops.plan_chunks(seg, n, br, be))
    windows, wide_rows = ops.window_steps(seg, n, br, be, *work)
    assert (wide_rows > 0) == (case == "wide")
    if case == "several-windows":
        assert windows == kseg.WIDE_WINDOWS
    rng = np.random.default_rng(len(case))
    for combiner, dtype in [("min", np.int32), ("max", np.int32),
                            ("sum", np.int32), ("sum", np.float32)]:
        for d in (1, 2):
            vals = jnp.asarray(
                rng.integers(-50, 50, (len(seg), d)).astype(dtype))
            got = ops.segment_combine(vals, jnp.asarray(seg), n, combiner,
                                      use_kernel=True, assume_sorted=True,
                                      block_rows=br, block_edges=be,
                                      work_list=work)
            want = ref.segment_combine_ref(vals, jnp.asarray(seg), n,
                                           combiner)
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_window_steps_counts_the_body_work():
    """``ScatterPlan.window_steps`` equals a lane-row-by-lane-row count of
    the body's windows and wide rows on a skewed plan, at the plan's own
    block sizes."""
    from repro.graph import generators as gen, pgraph

    g = gen.rmat(10, edge_factor=8, seed=1).symmetrized()
    plan = pgraph.partition_graph(g, 2, "random",
                                  build=("scatter_out",)).scatter_out
    br, be = plan.block_rows, plan.block_edges
    n_pad = -(-plan.u_cap // br) * br
    got = plan.window_steps
    assert got.shape == (2, 2) and (got > 0).all()  # both paths taken
    for w in range(plan.num_workers):
        seg = [s if s < plan.u_cap else n_pad for s in plan.edge_seg[w]]
        seg += [n_pad] * (-len(seg) % be)
        windows = wide = 0
        for b, c in zip(plan.item_block[w], plan.item_chunk[w]):
            for start in range(c * be, (c + 1) * be, 128) if c >= 0 else ():
                # the rows from the lane row's first id to its last,
                # clipped to the block
                lo = max(seg[start] - b * br, 0)
                hi = min(seg[start + 127] - b * br, br - 1)
                if lo <= hi:
                    span = hi // 8 - lo // 8 + 1
                    if span > kseg.WIDE_WINDOWS:
                        wide += 1
                    else:
                        windows += span
        assert tuple(got[w]) == (windows, wide)


@settings(max_examples=40, deadline=None)
@given(
    e=st.integers(1, 3000),
    n=st.integers(1, 3000),
    hub=st.floats(0.0, 0.9),
    br=st.sampled_from([8, 32, 128]),
    be=st.sampled_from([64, 128, 512]),
    seed=st.integers(0, 2**31 - 1),
)
def test_work_list_property(e, n, hub, br, be, seed):
    """Every plan's work list stays within NB + EC items, lists every row
    block, runs in block order, and gives each block exactly its
    covering chunks, ascending; the rest fetch a chunk and combine
    nothing."""
    rng = np.random.default_rng(seed)
    seg = rng.integers(0, n + 2, e)  # ids >= n are dropped
    seg[: int(hub * e)] = rng.integers(0, n)
    seg = np.sort(seg).astype(np.int32)
    start, count, ec = ops.plan_chunks(seg, n, br, be)
    blk, chunk = ops.build_work_list(start, count, ec)
    nb = len(start)
    assert len(blk) <= nb + ec
    assert np.array_equal(np.unique(blk), np.arange(nb))
    assert (np.diff(blk) >= 0).all()
    fetched = np.where(chunk < 0, ~chunk, chunk)
    assert ((fetched >= 0) & (fetched < ec)).all()
    assert (np.diff(fetched) >= 0).all()  # no chunk is fetched twice
    padded = np.where(seg < n, seg, nb * br)  # the kernel's view
    for b in range(nb):
        edges = np.flatnonzero(padded // br == b)
        want = (np.arange(edges[0] // be, edges[-1] // be + 1)
                if len(edges) else [])
        np.testing.assert_array_equal(chunk[(blk == b) & (chunk >= 0)], want)
    longer = ops.build_work_list(start, count, ec, len(blk) + 3)
    assert (longer[1][len(blk):] < 0).all()
    assert (longer[0][len(blk):] == blk[-1]).all()


BUCKET_CASES = {
    # id: (m, num_buckets, block_msgs, lanes Q or None)
    "single-bucket": (700, 1, 1024, None),
    "m-not-multiple": (3000, 8, 1024, None),
    "narrow-chunks": (1000, 4, 128, None),
    "lanes-q8": (2500, 8, 1024, 8),
    "lanes-single-bucket": (300, 1, 128, 3),
}


@pytest.mark.parametrize("case", sorted(BUCKET_CASES))
def test_bucket_ranks_kernel_exact(case):
    m, b, bm, q = BUCKET_CASES[case]
    rng = np.random.default_rng(m + b)
    keys = jnp.asarray(rng.integers(0, b + 1, m).astype(np.int32))
    if q is None:
        got = ops.bucket_ranks(keys, b, use_kernel=True, block_msgs=bm)
        want = ref.bucket_ranks_ref(keys, b)
    else:
        lanes = jnp.asarray(rng.random((m, q)) < 0.5) \
            & (keys < b)[:, None]
        got = ops.bucket_ranks_lanes(keys, lanes, b, use_kernel=True,
                                     block_msgs=bm)
        want = ref.bucket_ranks_lanes_ref(keys, lanes, b)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
