"""The plain references agree with the program at a small size (the CPU,
Pallas in interpret mode), and their controls disagree."""
import numpy as np
import pytest
import scipy.sparse
import scipy.sparse.csgraph

import check
import graph500
import graphs
from reference import bfs, components


@pytest.fixture(scope="module", params=[101, 2**33 + 7, 424242])
def g(request):
    return graph500.generate(9, 16, request.param)


def scipy_components(g):
    adj = scipy.sparse.coo_matrix(
        (np.ones(g.num_edges), (g.src, g.dst)), shape=(g.n, g.n))
    _, lab = scipy.sparse.csgraph.connected_components(adj, directed=False)
    return check.canonical(lab)


def test_components_match_scipy(g):
    ref = components.components(g.n, g.src, g.dst)
    np.testing.assert_array_equal(ref, scipy_components(g))
    np.testing.assert_array_equal(check.canonical(ref), ref)


def test_bfs_matches_scipy(g):
    csr = bfs.CSR(g.n, g.src, g.dst)
    adj = scipy.sparse.csr_matrix(
        (np.ones(g.num_edges), (g.src, g.dst)), shape=(g.n, g.n))
    for s in graphs.search_keys(g, 3, 5):
        want = scipy.sparse.csgraph.shortest_path(
            adj, unweighted=True, indices=int(s))
        want = np.where(np.isinf(want), -1, want).astype(np.int64)
        np.testing.assert_array_equal(bfs.hops(csr, int(s)), want)


def test_controls_fail(g):
    ref = components.components(g.n, g.src, g.dst)
    short = components.components_one_round_short(g.n, g.src, g.dst)
    assert check.label_mismatch(short, ref) > 0
    csr = bfs.CSR(g.n, g.src, g.dst)
    for s in graphs.search_keys(g, 3, 5):
        want = bfs.hops(csr, int(s))
        got = bfs.hops_one_level_short(csr, int(s))
        assert check.hop_mismatch(np.where(got < 0, check.UNREACHED, got),
                                  want) > 0


def _partitioned(g, key):
    from repro.algorithms import REGISTRY
    from repro.graph import pgraph
    from repro.graph.generators import EdgeList

    edges = EdgeList(g.n, np.stack([g.src, g.dst], 1).astype(np.int64),
                     None, directed=False)
    return pgraph.partition_graph(edges, 8, "random",
                                  build=REGISTRY[key].build)


@pytest.mark.parametrize("key", ["sv:composed", "wcc:prop"])
def test_components_agree_with_engine(g, key):
    from repro.algorithms import get_program
    from repro.pregel.engine import Engine

    res = Engine(mode="fused").run(get_program(key), _partitioned(g, key))
    ref = components.components(g.n, g.src, g.dst)
    assert check.label_mismatch(res.output, ref) == 0


def test_bfs_agrees_with_engine_serve(g):
    from repro.algorithms import get_program
    from repro.pregel.engine import Engine
    from repro.pregel.serve import QueryQueue

    sources = graphs.search_keys(g, 5, 12).tolist()
    res = Engine(mode="chunked", chunk_size=4).serve(
        get_program("reach:basic"), _partitioned(g, "reach:basic"),
        QueryQueue.from_queries(sources), num_lanes=8)
    csr = bfs.CSR(g.n, g.src, g.dst)
    assert [r.status for r in res.records] == ["ok"] * len(sources)
    for r in res.records:
        assert check.hop_mismatch(r.output, bfs.hops(csr, r.query)) == 0
