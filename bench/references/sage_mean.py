"""Plain GraphSAGE-mean (Hamilton et al. 2017), full neighbourhood, in
jax.numpy, edge-list form.

    H' = act([mean_{u in N(v) + v} H_u ; H_v] W)

The mean runs over the in-neighbours and the node itself (one self loop
per node, weight 1 / deg_in(v) with the loop counted); relu between
layers, logits at the end, no bias and no embedding normalisation, as the
configuration states. Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness.precision import Numerics


def layer_dims(cfg: dict) -> list[tuple[int, int]]:
    g = cfg["graph"]
    dims = ([g["feature_dim"]] + [cfg["hidden_dim"]] * (cfg["num_layers"] - 1)
            + [g["num_classes"]])
    return list(zip(dims[:-1], dims[1:]))


def param_shapes(cfg: dict) -> list[dict]:
    """The program's parameter layout: one ``w`` of (2 d_in, d_out) per
    layer over the concatenation [mean ; self]."""
    return [{"w": (2 * din, dout)} for din, dout in layer_dims(cfg)]


def ops(cfg: dict) -> list[tuple]:
    out = []
    for din, dout in layer_dims(cfg):
        out += [("agg", din), ("dense", 2 * din, dout)]
    return out


def edge_weights(edges: np.ndarray, num_nodes: int) -> tuple:
    loops = np.arange(num_nodes, dtype=np.int64)
    src = np.concatenate([edges[:, 0], loops])
    dst = np.concatenate([edges[:, 1], loops])
    deg_in = np.bincount(dst, minlength=num_nodes).astype(np.float64)
    w = 1.0 / np.maximum(deg_in[dst], 1.0)
    return src.astype(np.int32), dst.astype(np.int32), w.astype(np.float32)


def forward(params: dict, x, src, dst, w, num_nodes: int,
            num: Numerics) -> jax.Array:
    h = num.cast(x)
    layers = params["layers"]
    for i, layer in enumerate(layers):
        agg = num.aggregate(h, src, dst, w, num_nodes)
        h = num.matmul(jnp.concatenate([agg, h], axis=-1), layer["w"])
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h.astype(jnp.float32)
