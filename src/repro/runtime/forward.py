"""Zoo-model forward pass on the GNNerator engines (runtime internals).

This is the single implementation behind :meth:`Executable.forward` and the
deprecated ``repro.gnn.models.zoo_forward`` shim. Per layer, an
executor-provided :class:`repro.gnn.executor.LayerPlan` picks the feature
block size B and whether the two stages run fused (h_agg never leaves
VMEM) or two-stage through feature memory; the kernel backend is threaded
explicitly so a compiled Executable is pinned to one backend regardless of
later env changes.

The GAT attention weights are computed per shard pair as an (S, S, n, n)
head-block tensor and fed straight to the shard-grid SpMM kernel — the
aggregation stays on the Graph Engine; only the masked softmax runs on the
activation unit (plain jnp here).
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engines import (DenseEngine, GNNeratorController, GraphEngine,
                                GraphTensors)
from repro.core.sharding import shard_graph
from repro.gnn.models import ZooSpec, graph_signature
from repro.kernels.registry import KernelBackend


def build_graph_tensors(edges: np.ndarray, num_nodes: int, n: int,
                        arch: str) -> GraphTensors:
    """Shard + normalize a graph for the given zoo architecture."""
    norm, loops = graph_signature(arch)
    sg = shard_graph(edges, num_nodes, n, normalize=norm,
                     add_self_loops=loops)
    return GraphTensors.from_sharded(sg)


def layer_activation(spec: ZooSpec, i: int) -> str:
    """Activation for layer i: relu between layers, logits at the end.
    Shared with the sharded forward (dist/gnn.py) so the two execution
    paths can never disagree on where nonlinearities sit."""
    return "relu" if i < len(spec.layer_dims) - 1 else "none"


# architectures whose layers are linear up to their activation, so that
# the controller may run either engine first (``linear_layer``)
LINEAR_ARCHS = ("gcn", "sage_mean")


def _controller(plan, backend: KernelBackend | None) -> GNNeratorController:
    b = plan.B if plan is not None else 128
    fused = plan.fused if plan is not None else True
    return GNNeratorController(dense=DenseEngine(backend=backend),
                               graph=GraphEngine(block_b=b, backend=backend),
                               fuse=fused)


def _gat_attention_blocks(gt: GraphTensors, z_head: jax.Array,
                          s_src: jax.Array, s_dst: jax.Array,
                          negative_slope: float) -> jax.Array:
    """Per-head attention weights laid out on the shard grid.

    z_head: (S, n, F) head features; s_src/s_dst: (S, n) attention scores.
    Returns α as (S, S, n, n) blocks [dst_shard, src_shard, v, u] ready for
    the shard-grid SpMM kernel.
    """
    mask = gt.blocks != 0                                   # (S, S, n, n)
    logits = s_dst[:, None, :, None] + s_src[None, :, None, :]
    logits = jax.nn.leaky_relu(logits, negative_slope)
    logits = jnp.where(mask, logits, -jnp.inf)
    # masked softmax over ALL of v's in-neighbors: axes (src_shard, u)
    m = jnp.max(logits, axis=(1, 3), keepdims=True)
    m = jnp.where(jnp.isfinite(m), m, 0.0)
    e = jnp.where(mask, jnp.exp(logits - m), 0.0)
    denom = jnp.sum(e, axis=(1, 3), keepdims=True)
    return jnp.where(denom > 0, e / jnp.maximum(denom, 1e-30), 0.0)


def _gat_layer(spec: ZooSpec, layer: dict, gt: GraphTensors, h: jax.Array,
               ctrl: GNNeratorController, *, activation: str) -> jax.Array:
    s, n, din = h.shape
    heads, hd = layer["a_src"].shape
    z = ctrl.dense(h.reshape(s * n, din), layer["w"])       # (S·n, H·hd)
    z = z.reshape(s, n, heads, hd)
    s_src = jnp.einsum("snhf,hf->snh", z.astype(jnp.float32),
                       layer["a_src"].astype(jnp.float32))
    s_dst = jnp.einsum("snhf,hf->snh", z.astype(jnp.float32),
                       layer["a_dst"].astype(jnp.float32))
    outs = []
    for hix in range(heads):   # heads stay sequential: one α grid in VMEM
        alpha = _gat_attention_blocks(gt, z[..., hix, :],
                                      s_src[..., hix], s_dst[..., hix],
                                      spec.negative_slope)
        outs.append(ctrl.graph.spmm(alpha, z[..., hix, :]))
    out = jnp.concatenate(outs, axis=-1)                    # (S, n, H·hd)
    if activation == "relu":
        out = jax.nn.relu(out)
    return out


def _layer(spec: ZooSpec, layer: dict, gt: GraphTensors, h: jax.Array,
           ctrl: GNNeratorController, act: str) -> jax.Array:
    """One layer of ``spec.arch``, each stage under the named scope
    (``aggregate``, ``extract``, ``fused`` or ``attention``) that its
    operations carry in the compiled program's metadata."""
    if spec.arch in LINEAR_ARCHS:
        # linear_layer orders and scopes its own stages
        return ctrl.linear_layer(gt, h, layer["w"], activation=act,
                                 concat_self=spec.arch == "sage_mean")
    if spec.arch == "gat":
        with jax.named_scope("attention"):
            return _gat_layer(spec, layer, gt, h, ctrl, activation=act)
    s, n, d = h.shape
    if spec.arch == "sage_max":
        with jax.named_scope("extract"):
            z = ctrl.dense(h.reshape(s * n, d), layer["w_pool"],
                           layer["b_pool"], activation="relu")
        with jax.named_scope("aggregate"):
            zbar = ctrl.graph.aggregate(gt, z.reshape(s, n, d), op="max")
        with jax.named_scope("extract"):
            cat = jnp.concatenate([zbar, h], axis=-1).reshape(s * n, 2 * d)
            return ctrl.dense(cat, layer["w"],
                              activation=act).reshape(s, n, -1)
    if spec.arch == "gin":
        with jax.named_scope("aggregate"):
            agg = ctrl.graph.aggregate(gt, h, op="linear")  # Σ, no self loop
        with jax.named_scope("extract"):
            x = (1.0 + layer["eps"]) * h + agg
            hid = ctrl.dense(x.reshape(s * n, d), layer["w1"], layer["b1"],
                             activation="relu")
            return ctrl.dense(hid, layer["w2"], layer["b2"],
                              activation=act).reshape(s, n, -1)
    return h


def producer_orders(spec: ZooSpec,
                    plans: Sequence | None = None) -> list[tuple[str, int]]:
    """``(order, grid walks)`` of each layer as :func:`forward` runs it:
    ``dense-first``, ``graph-first`` or ``graph-first fused``. Empty for
    an architecture outside ``LINEAR_ARCHS``, whose order is fixed."""
    if spec.arch not in LINEAR_ARCHS:
        return []
    out = []
    for i, (din, dout) in enumerate(spec.layer_dims):
        ctrl = _controller(plans[i] if plans is not None else None, None)
        order, walks = ctrl.producer_order(din, dout)
        fused = order == "graph-first" and spec.arch == "gcn" and ctrl.fuse
        out.append((order + " fused" if fused else order, walks))
    return out


def forward(spec: ZooSpec, params: dict, gt: GraphTensors,
            h: jax.Array, *, plans: Sequence | None = None,
            backend: KernelBackend | None = None) -> jax.Array:
    """Run the model; h is (S, n, in_dim) shard-grouped (GraphTensors.group).

    ``plans`` is an optional per-layer sequence of LayerPlans from
    repro.gnn.executor; None falls back to the default controller (fused
    where legal, B=128). ``backend=None`` resolves per call from the
    kernel registry (env-var selectable).
    """
    for i, layer in enumerate(params["layers"]):
        plan = plans[i] if plans is not None else None
        ctrl = _controller(plan, backend)
        act = layer_activation(spec, i)
        with jax.named_scope(f"layer{i}"):
            h = _layer(spec, layer, gt, h, ctrl, act)
    return gt.ungroup(h)
