"""The benchmark's own traffic generators: edge deltas, node requests
and arrival times.

Edge deltas follow the program's random-delta model (delete existing
rows, insert pairs between degree-weighted endpoints) without its rebuild
of the whole pair set per delta: ``delta_pool`` draws, over one hash set
of the edges, a set of deltas that are valid in any order, so that every
seed can be offered the same work in another order.
"""
from __future__ import annotations

import numpy as np


def pair_key(u: int, v: int) -> int:
    return (int(u) << 32) | int(v)


def delta_pool(edges: np.ndarray, rng: np.random.Generator, count: int, *,
               deletes: int, inserts: int) -> list:
    """``count`` deltas that are valid in any order against ``edges``:
    the deleted rows are distinct rows of ``edges`` (uniform over rows,
    which weights their endpoints by degree), the inserted pairs distinct,
    not in ``edges`` and not self loops, joining a degree-weighted source
    to a degree-weighted destination. With ``deletes == inserts`` every
    order keeps the edge count level."""
    e = np.asarray(edges, dtype=np.int64)
    keys = set(((e[:, 0] << 32) | e[:, 1]).tolist())
    rows = rng.choice(e.shape[0], size=count * deletes, replace=False)
    taken: set = set()
    pool = []
    for i in range(count):
        adds: list[tuple[int, int]] = []
        while len(adds) < inserts:
            u = int(e[rng.integers(e.shape[0]), 0])
            v = int(e[rng.integers(e.shape[0]), 1])
            k = pair_key(u, v)
            if u == v or k in keys or k in taken:
                continue
            taken.add(k)
            adds.append((u, v))
        pool.append((e[rows[i * deletes:(i + 1) * deletes]].copy(),
                     np.array(adds, dtype=np.int64).reshape(-1, 2)))
    return pool


def shuffled_arrivals(fixed: np.random.Generator, rng: np.random.Generator,
                      rate: float, seconds: float) -> np.ndarray:
    """Arrival offsets (s) over ``seconds``: one set of exponential gaps of
    mean 1/``rate`` drawn from ``fixed``, in an order drawn from ``rng``,
    so every seed offers the same count and the same gaps."""
    gaps = fixed.exponential(1.0 / rate, size=int(rate * seconds * 1.5) + 64)
    gaps = gaps[np.cumsum(gaps) < seconds]
    return np.cumsum(rng.permutation(gaps))


def apply_delta(edges: np.ndarray, dels: np.ndarray,
                adds: np.ndarray) -> np.ndarray:
    """Edge list after one delta: every row matching a deleted pair goes,
    inserted rows are appended."""
    keys = (edges[:, 0] << 32) | edges[:, 1]
    dkeys = (dels[:, 0] << 32) | dels[:, 1]
    return np.concatenate([edges[~np.isin(keys, dkeys)], adds], axis=0)


def zipf_by_degree(edges: np.ndarray, num_nodes: int, s: float) -> tuple:
    """``(node_order, cdf)``: nodes ranked by total degree (ties by id), and
    the cumulative Zipf(s) weight of each rank, so hubs are drawn most."""
    deg = np.bincount(edges.reshape(-1), minlength=num_nodes)
    order = np.lexsort((np.arange(num_nodes), -deg))
    w = 1.0 / np.arange(1, num_nodes + 1, dtype=np.float64) ** s
    cdf = np.cumsum(w)
    return order, cdf / cdf[-1]


def node_requests(rng: np.random.Generator, sizes: np.ndarray,
                  order: np.ndarray, cdf: np.ndarray) -> list:
    """One request per entry of ``sizes``, of that many node ids drawn from
    the Zipf ranking."""
    ranks = np.searchsorted(cdf, rng.random(int(sizes.sum())), side="right")
    ids = order[np.minimum(ranks, len(order) - 1)]
    return np.split(ids, np.cumsum(sizes)[:-1])
