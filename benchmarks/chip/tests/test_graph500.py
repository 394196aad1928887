"""The generator is the benchmark's data: it must not move."""
import numpy as np
import pytest

import graph500
import graphs


@pytest.fixture(scope="module")
def g():
    return graph500.generate(10, 16, 2**40 + 12345)


def test_same_seed_same_graph(g):
    again = graph500.generate(10, 16, 2**40 + 12345)
    np.testing.assert_array_equal(g.src, again.src)
    np.testing.assert_array_equal(g.dst, again.dst)


def test_other_seed_other_graph(g):
    other = graph500.generate(10, 16, 12345)
    assert g.num_edges != other.num_edges or \
        not np.array_equal(g.src, other.src)


def test_large_seeds_differ_above_32_bits():
    a = graph500.generate(8, 16, 5)
    b = graph500.generate(8, 16, 5 + 2**32)
    assert not np.array_equal(a.dst, b.dst)


def test_undirected_simple_sorted(g):
    assert g.n == 1024
    assert np.all(g.src != g.dst)
    keys = g.src.astype(np.int64) * g.n + g.dst
    assert np.all(np.diff(keys) > 0)          # sorted, no duplicates
    rev = np.sort(g.dst.astype(np.int64) * g.n + g.src)
    np.testing.assert_array_equal(rev, keys)  # every edge both ways


def test_edge_count_near_graph500(g):
    # 2 * 16 * n generated directed edges, less self-loops and duplicates
    assert 0.6 * 32 * g.n < g.num_edges < 32 * g.n


def test_labels_are_permuted(g):
    # without the permutation vertex 0 is the Kronecker graph's hub
    deg = g.degrees()
    assert int(np.argmax(deg)) != 0
    assert deg[0] < deg.max()


def test_search_keys_have_edges(g):
    keys = graphs.search_keys(g, 7, 500)
    assert np.all(g.degrees()[keys] >= 1)
    np.testing.assert_array_equal(keys, graphs.search_keys(g, 7, 500))
    assert len(np.unique(keys)) > 100


def test_negative_seed_refused():
    with pytest.raises(ValueError):
        graph500.generate(8, 16, -1)


def test_initiator_is_read():
    a = graph500.generate(8, 16, 5)
    b = graph500.generate(8, 16, 5, (0.45, 0.15, 0.15))
    assert a.num_edges != b.num_edges or not np.array_equal(a.dst, b.dst)
    with pytest.raises(ValueError):
        graph500.generate(8, 16, 5, (0.6, 0.3, 0.3))


def test_cached_reads_back_what_it_wrote(tmp_path):
    spec = {"generator": "graph500_kronecker", "scale": 8, "edge_factor": 16,
            "seed": 5, "initiator": list(graph500.INITIATOR),
            "undirected": True}
    made = []

    def make(spec):
        made.append(spec)
        return graph500.generate(spec["scale"], spec["edge_factor"],
                                 spec["seed"], tuple(spec["initiator"]))

    first = graphs.cached(spec, tmp_path, make)
    again = graphs.cached(spec, tmp_path, make)
    other = graphs.cached(dict(spec, seed=6), tmp_path, make)
    assert len(made) == 2 and again.n == first.n == 256
    np.testing.assert_array_equal(again.src, first.src)
    np.testing.assert_array_equal(again.dst, first.dst)
    assert not np.array_equal(other.dst, first.dst)
