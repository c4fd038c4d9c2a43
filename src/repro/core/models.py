"""The paper's GNN benchmarks (Table III): GCN, Graphsage, GraphsagePool.

Functional models: ``init_*`` builds a param pytree, ``apply_*`` runs the
forward pass on shard-grouped features via the GNNerator engines. All three
follow the paper's topology — one hidden layer of dimension 16 by default —
but depth/width are configurable (the scaling benchmarks sweep them).

GCN        : H' = relu(Â H W)
Graphsage  : z̄ = mean_{N(u)∪u} h ; h' = relu(W [z̄; h])
GraphsagePool: z = relu(W_pool h) ; z̄ = max z ; h' = relu(W [z̄; h])
                                                     (dense-first!)

GCN and Graphsage are linear up to their activation: the controller runs
them graph-first (GCN fused) or dense-first, whichever walks the shard grid
fewer times (``GNNeratorController.linear_layer``).
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engines import GNNeratorController, GraphTensors
from repro.core.sharding import ShardedGraph, shard_graph


@dataclasses.dataclass(frozen=True)
class GNNSpec:
    kind: str                 # gcn | graphsage | graphsage_pool
    in_dim: int
    hidden_dim: int
    out_dim: int
    num_hidden_layers: int = 1   # paper Table III: 1

    @property
    def layer_dims(self) -> list[tuple[int, int]]:
        dims = [self.in_dim] + [self.hidden_dim] * self.num_hidden_layers + [self.out_dim]
        return list(zip(dims[:-1], dims[1:]))


def _glorot(key, shape):
    fan_in, fan_out = shape[0], shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


def init_gnn(key: jax.Array, spec: GNNSpec) -> dict:
    params: dict = {"layers": []}
    for i, (din, dout) in enumerate(spec.layer_dims):
        key, k1, k2 = jax.random.split(key, 3)
        if spec.kind == "gcn":
            layer = {"w": _glorot(k1, (din, dout))}
        elif spec.kind == "graphsage":
            layer = {"w": _glorot(k1, (2 * din, dout))}
        elif spec.kind == "graphsage_pool":
            layer = {
                "w_pool": _glorot(k1, (din, din)),
                "w": _glorot(k2, (2 * din, dout)),
            }
        else:
            raise ValueError(spec.kind)
        params["layers"].append(layer)
    return params


def build_graph_tensors(sg_edges: np.ndarray, num_nodes: int, n: int,
                        kind: str) -> GraphTensors:
    """Shard + normalize a graph for the given model kind."""
    norm = {"gcn": "gcn", "graphsage": "mean", "graphsage_pool": "max"}[kind]
    sg: ShardedGraph = shard_graph(sg_edges, num_nodes, n, normalize=norm,
                                   add_self_loops=True)
    return GraphTensors.from_sharded(sg)


def make_forward(spec: GNNSpec,
                 controller: GNNeratorController | None = None
                 ) -> Callable[[dict, GraphTensors, jax.Array], jax.Array]:
    """Build apply(params, gt, h_grouped) -> logits (N, out_dim)."""
    ctrl = controller or GNNeratorController()
    n_layers = len(spec.layer_dims)

    def apply(params: dict, gt: GraphTensors, h: jax.Array) -> jax.Array:
        # h: (S, n, in_dim) shard-grouped (see GraphTensors.group)
        for i, layer in enumerate(params["layers"]):
            act = "relu" if i < n_layers - 1 else "none"
            if spec.kind in ("gcn", "graphsage"):   # mean norm for sage
                h = ctrl.linear_layer(gt, h, layer["w"], activation=act,
                                      concat_self=spec.kind == "graphsage")
            elif spec.kind == "graphsage_pool":
                zbar = ctrl.dense_first(gt, h, layer["w_pool"],
                                        activation="relu", agg="max")
                s, n, d = h.shape
                cat = jnp.concatenate([zbar, h], axis=-1).reshape(s * n, 2 * d)
                h = ctrl.dense(cat, layer["w"], activation=act).reshape(s, n, -1)
        return gt.ungroup(h)

    return apply


PAPER_NETWORKS = {  # Table III
    "gcn": dict(kind="gcn", hidden_dim=16, num_hidden_layers=1),
    "graphsage": dict(kind="graphsage", hidden_dim=16, num_hidden_layers=1),
    "graphsage_pool": dict(kind="graphsage_pool", hidden_dim=16,
                           num_hidden_layers=1),
}


def paper_spec(network: str, in_dim: int, num_classes: int) -> GNNSpec:
    cfg = PAPER_NETWORKS[network]
    return GNNSpec(in_dim=in_dim, out_dim=num_classes, **cfg)
