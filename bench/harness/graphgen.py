"""The benchmark's own graph generator: Table-II profiles by preferential
attachment, deterministic per seed.

A copy of the program's generator (``repro.graphs.datasets``: the
preferential-attachment edge sampler and ``make_dataset``), kept here so
that a change to the program's data code cannot change what the benchmark
measures. It draws the same numbers in the same order, so for a given seed
it gives the same edges, features, labels and train mask.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Graph:
    num_nodes: int
    num_edges: int
    feature_dim: int
    num_classes: int
    edges: np.ndarray       # (E, 2) int64 (src, dst), both directions present
    features: np.ndarray    # (N, F) float32
    labels: np.ndarray      # (N,) int32
    train_mask: np.ndarray  # (N,) bool


def preferential_attachment_edges(n: int, e_target: int,
                                  rng: np.random.Generator) -> np.ndarray:
    """Undirected preferential-attachment edge list with ~e_target/2 unique
    undirected edges, returned with both directions."""
    m = max(1, min(e_target // (2 * n), n - 1))
    extra = e_target // 2 - m * (n - m)
    targets = list(range(m))
    repeated: list[int] = list(range(m))
    edges = []
    for v in range(m, n):
        for t in set(targets):
            edges.append((v, t))
            repeated.extend([v, t])
        idx = rng.integers(0, len(repeated), size=m)
        targets = [repeated[i] for i in idx]
    repeated_arr = np.array(repeated)
    while extra > 0:
        k = min(extra, 4096)
        a = repeated_arr[rng.integers(0, len(repeated_arr), size=k)]
        b = rng.integers(0, n, size=k)
        mask = a != b
        for u, v in zip(a[mask], b[mask]):
            edges.append((int(u), int(v)))
        extra -= int(mask.sum())
    e = np.array(edges, dtype=np.int64)
    und = np.unique(np.sort(e, axis=1), axis=0)
    return np.concatenate([und, und[:, ::-1]], axis=0)


def make_graph(profile: dict, seed: int,
               edges: np.ndarray | None = None) -> Graph:
    """Generate the graph a configuration's ``graph`` block describes
    (``num_nodes``, ``num_edges``, ``feature_dim``, ``num_classes``).

    With ``edges=None`` this is the program's generator exactly. Given
    ``edges``, the topology is kept and the seed draws the rest."""
    n, e_target = int(profile["num_nodes"]), int(profile["num_edges"])
    f, c = int(profile["feature_dim"]), int(profile["num_classes"])
    rng = np.random.default_rng(seed)
    if edges is None:
        edges = preferential_attachment_edges(n, e_target, rng)
    feats = rng.standard_normal((n, f), dtype=np.float32)
    feats /= np.linalg.norm(feats, axis=1, keepdims=True) + 1e-6
    labels = rng.integers(0, c, size=n).astype(np.int32)
    planted = rng.standard_normal((c, f), dtype=np.float32)
    feats += 0.5 * planted[labels] / np.sqrt(f)
    train_mask = rng.random(n) < 0.6
    return Graph(n, e_target, f, c, edges, feats, labels, train_mask)


def benchmark_graph(profile: dict, seed: int) -> Graph:
    """The graph of a benchmark run: one fixed topology per configuration
    (drawn from its ``topology_seed``: a dataset is one graph, and its
    shapes fix the programs the run compiles), with features, labels and
    the train mask drawn from the run's seed."""
    n, e_target = int(profile["num_nodes"]), int(profile["num_edges"])
    edges = preferential_attachment_edges(
        n, e_target, np.random.default_rng(profile["topology_seed"]))
    return make_graph(profile, seed, edges=edges)
