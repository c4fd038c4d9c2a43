"""Plain GCN (Kipf & Welling 2017) in jax.numpy, edge-list form.

    H' = act(A_hat H W),   A_hat[v, u] = 1 / sqrt(deg_out(u) deg_in(v))

over the edges plus one self loop per node, degrees counting the self
loop; relu between layers, logits at the end, no bias. This imports
nothing of the program: the graph is the edge list, the weights the
benchmark's own.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.precision import Numerics


def param_shapes(cfg: dict) -> list[dict]:
    """The program's parameter layout: one ``w`` per layer."""
    return [{"w": (din, dout)} for din, dout in layer_dims(cfg)]


def layer_dims(cfg: dict) -> list[tuple[int, int]]:
    g = cfg["graph"]
    dims = ([g["feature_dim"]] + [cfg["hidden_dim"]] * (cfg["num_layers"] - 1)
            + [g["num_classes"]])
    return list(zip(dims[:-1], dims[1:]))


def ops(cfg: dict) -> list[tuple]:
    """The work one forward requires: per layer the aggregation of the
    input features, then the dense extraction."""
    out = []
    for din, dout in layer_dims(cfg):
        out += [("agg", din), ("dense", din, dout)]
    return out


def edge_weights(edges: np.ndarray, num_nodes: int) -> tuple:
    """``(src, dst, w)`` over the edges and one self loop per node."""
    loops = np.arange(num_nodes, dtype=np.int64)
    src = np.concatenate([edges[:, 0], loops])
    dst = np.concatenate([edges[:, 1], loops])
    deg_out = np.bincount(src, minlength=num_nodes).astype(np.float64)
    deg_in = np.bincount(dst, minlength=num_nodes).astype(np.float64)
    w = 1.0 / np.sqrt(np.maximum(deg_out[src], 1.0)
                      * np.maximum(deg_in[dst], 1.0))
    return src.astype(np.int32), dst.astype(np.int32), w.astype(np.float32)


def forward(params: dict, x, src, dst, w, num_nodes: int,
            num: Numerics) -> jax.Array:
    h = num.cast(x)
    layers = params["layers"]
    for i, layer in enumerate(layers):
        z = num.matmul(h, layer["w"])
        h = num.aggregate(z, src, dst, w, num_nodes)
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h.astype(jnp.float32)
