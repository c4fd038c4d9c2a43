"""Plain GAT (Veličković et al. 2018, arXiv:1710.10903) in jax.numpy,
edge-list form.

    z = H W,  e_vu = LeakyReLU(a_dst · z_v + a_src · z_u)  per head
    α_vu = softmax over u in N(v) + v of e_vu
    H' = elu(‖_heads Σ_u α_vu z_u)          hidden layers, concatenated
    logits = mean_heads Σ_u α_vu z_u          output layer, averaged

over the edges plus one self loop per node. ``heads``, ``out_heads`` and
``negative_slope`` come from the configuration's ``spec``; no bias, no
dropout. The harness calls ``forward`` without the configuration, so the
slope is the published one, ``SLOPE``, and a configuration that states
another is refused when its parameters are laid out. The scores and the
softmax run in float32 whatever the numerics; the extraction and each
head's weighted sum go through ``Numerics``.
Imports nothing of the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import work
from bench.harness.precision import Numerics

# LeakyReLU's negative slope in the scores (the paper's 0.2)
SLOPE = 0.2


def layer_dims(cfg: dict) -> list[tuple[int, int]]:
    g = cfg["graph"]
    dims = ([g["feature_dim"]] + [cfg["hidden_dim"]] * (cfg["num_layers"] - 1)
            + [g["num_classes"]])
    return list(zip(dims[:-1], dims[1:]))


def head_dims(cfg: dict) -> list[tuple[int, int, int]]:
    """``(d_in, heads, features per head)`` of each layer: hidden layers
    split their width over ``heads``, the output layer has ``out_heads``
    heads of the class count."""
    spec = cfg["spec"]
    if spec["negative_slope"] != SLOPE:
        raise ValueError(f"the GAT reference computes LeakyReLU with slope "
                         f"{SLOPE}, not {spec['negative_slope']}")
    dims = layer_dims(cfg)
    out = []
    for i, (din, dout) in enumerate(dims):
        if i < len(dims) - 1:
            out.append((din, spec["heads"], dout // spec["heads"]))
        else:
            out.append((din, spec["out_heads"], dout))
    return out


def param_shapes(cfg: dict) -> list[dict]:
    """The program's parameter layout: per layer ``w`` of (d_in, heads·F)
    and the two score vectors ``a_src``, ``a_dst`` of (heads, F)."""
    return [{"w": (din, k * f), "a_src": (k, f), "a_dst": (k, f)}
            for din, k, f in head_dims(cfg)]


def ops(cfg: dict) -> list[tuple]:
    """The work one forward requires: per layer the extraction of every
    head, then the attention, counted per edge (self loops included) and
    per head as a score, its LeakyReLU, the max, the exponent, the sum,
    the division and the 2F of the weighted sum, plus the two score
    projections of 2 F per node and head. Its bytes are the edge list
    (work.py's 8 per nonzero), z, the two scores and the output, each
    once."""
    n, z = cfg["graph"]["num_nodes"], work.nnz(cfg)
    out = []
    for din, k, f in head_dims(cfg):
        flops = z * k * (6.0 + 2 * f) + 2.0 * n * k * f * 2
        nbytes = z * 8.0 + 4.0 * n * (2 * k * f + 2 * k)
        out += [("dense", din, k * f), ("counted", "attention", flops, nbytes)]
    return out


def edge_weights(edges: np.ndarray, num_nodes: int) -> tuple:
    """``(src, dst, w)`` over the edges and one self loop per node; the
    weights are 1 (attention supplies its own)."""
    loops = np.arange(num_nodes, dtype=np.int64)
    src = np.concatenate([edges[:, 0], loops])
    dst = np.concatenate([edges[:, 1], loops])
    return (src.astype(np.int32), dst.astype(np.int32),
            np.ones(len(src), np.float32))


def _attend(zh, a_src, a_dst, src, dst, num_nodes: int, slope: float,
            num: Numerics):
    """(N, H, F) -> (N, H, F): every head's softmax over each node's
    in-edges and its weighted sum."""
    hi = jax.lax.Precision.HIGHEST
    zf = zh.astype(jnp.float32)
    s_src = jnp.einsum("nhf,hf->nh", zf, a_src.astype(jnp.float32),
                       precision=hi)
    s_dst = jnp.einsum("nhf,hf->nh", zf, a_dst.astype(jnp.float32),
                       precision=hi)
    e = jax.nn.leaky_relu(s_dst[dst] + s_src[src], slope)        # (E, H)
    m = jax.ops.segment_max(e, dst, num_segments=num_nodes)
    p = jnp.exp(e - m[dst])
    denom = jax.ops.segment_sum(p, dst, num_segments=num_nodes)
    alpha = p / denom[dst]
    heads = [num.aggregate(zh[:, k], src, dst, alpha[:, k], num_nodes)
             for k in range(zh.shape[1])]
    return jnp.stack(heads, axis=1).astype(jnp.float32)


def forward(params: dict, x, src, dst, w, num_nodes: int,
            num: Numerics) -> jax.Array:
    h = num.cast(x)
    layers = params["layers"]
    for i, layer in enumerate(layers):
        k, f = layer["a_src"].shape
        z = num.matmul(h, layer["w"]).reshape(num_nodes, k, f)
        out = _attend(z, layer["a_src"], layer["a_dst"], src, dst,
                      num_nodes, SLOPE, num)
        if i < len(layers) - 1:
            h = num.cast(jax.nn.elu(out.reshape(num_nodes, k * f)))
        else:
            h = out.mean(axis=1)
    return h.astype(jnp.float32)
