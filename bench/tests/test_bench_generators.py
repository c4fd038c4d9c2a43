"""The benchmark's own generators: the graph generator is the program's,
draw for draw, and every delta is valid against the graph it meets."""
import numpy as np
import pytest

from bench.harness import deltas, graphgen

PUBMED = {"num_nodes": 19717, "num_edges": 88648, "feature_dim": 500,
          "num_classes": 3, "topology_seed": 0}


@pytest.mark.parametrize("seed", [0, 2 ** 33 + 5])
def test_graph_generator_matches_the_programs(seed):
    from repro.graphs.datasets import make_dataset
    want = make_dataset("pubmed", seed=seed)
    got = graphgen.make_graph(PUBMED, seed)
    assert np.array_equal(got.edges, want.edges)
    assert np.array_equal(got.features, want.features)
    assert np.array_equal(got.labels, want.labels)
    assert np.array_equal(got.train_mask, want.train_mask)


def test_benchmark_graph_keeps_one_topology():
    a = graphgen.benchmark_graph(PUBMED, 1)
    b = graphgen.benchmark_graph(PUBMED, 2)
    assert np.array_equal(a.edges, b.edges)
    assert not np.array_equal(a.features, b.features)


def _keys(edges):
    return set(((edges[:, 0] << 32) | edges[:, 1]).tolist())


@pytest.mark.parametrize("size,count", [((300, 1200), 150),
                                        ((19717, 88648), 40)])
def test_every_delta_is_valid_and_keeps_the_edge_count(size, count):
    from repro.graphs.delta import GraphDelta, apply_to_edge_list
    prof = dict(PUBMED, num_nodes=size[0], num_edges=size[1])
    g = graphgen.benchmark_graph(prof, 3)
    pool = deltas.delta_pool(g.edges, np.random.default_rng(9), count,
                             deletes=2, inserts=2)
    # any order of the pool is valid: apply it in a shuffled one
    order = np.random.default_rng(10).permutation(count)
    edges = g.edges
    for i in order:
        dels, adds = pool[i]
        live = _keys(edges)
        assert dels.shape == adds.shape == (2, 2)
        assert all(int(u) << 32 | int(v) in live for u, v in dels)
        assert len({int(u) << 32 | int(v) for u, v in dels}) == 2
        assert all(int(u) << 32 | int(v) not in live and u != v
                   for u, v in adds)
        assert len({int(u) << 32 | int(v) for u, v in adds}) == 2
        # the program's canonical application agrees and keeps the count
        new, _ = apply_to_edge_list(edges, g.num_nodes, GraphDelta(
            add_edges=adds, del_edges=dels))
        assert new.shape == edges.shape
        edges = deltas.apply_delta(edges, dels, adds)
        assert _keys(new) == _keys(edges)


def test_requests_and_arrivals():
    fixed, rng = np.random.default_rng(4), np.random.default_rng(5)
    g = graphgen.benchmark_graph(dict(PUBMED, num_nodes=400,
                                      num_edges=1600), 0)
    order, cdf = deltas.zipf_by_degree(g.edges, g.num_nodes, 1.0)
    deg = np.bincount(g.edges.reshape(-1), minlength=g.num_nodes)
    assert deg[order[0]] == deg.max()
    reqs = deltas.node_requests(rng, rng.integers(1, 65, size=500), order,
                                cdf)
    sizes = [len(r) for r in reqs]
    assert min(sizes) >= 1 and max(sizes) <= 64
    ids = np.concatenate(reqs)
    assert ids.min() >= 0 and ids.max() < g.num_nodes
    # Zipf over the degree ranking: the hub is the most requested node
    assert np.bincount(ids).argmax() == order[0]
    # every seed gets the same gaps in another order
    a = deltas.shuffled_arrivals(np.random.default_rng(4),
                                 np.random.default_rng(1), 200.0, 20.0)
    b = deltas.shuffled_arrivals(np.random.default_rng(4),
                                 np.random.default_rng(2), 200.0, 20.0)
    assert len(a) == len(b) and abs(len(a) - 4000) < 4 * 4000 ** 0.5
    assert a.max() < 20.0 and not np.array_equal(a, b)
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(b, prepend=0)))
