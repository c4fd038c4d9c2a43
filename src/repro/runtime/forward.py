"""Zoo-model forward pass on the GNNerator engines (runtime internals).

This is the single-device forward behind :meth:`Executable.forward` and
``runtime.fit``. Per layer, an executor-provided
:class:`repro.gnn.executor.LayerPlan` picks the feature block size B and
whether the two stages run fused (h_agg never leaves VMEM) or two-stage
through feature memory; the kernel backend is resolved once and threaded
explicitly so a compiled Executable is pinned to one backend regardless of
later env changes.

A GAT layer extracts z = H W for all heads on the Dense Engine, projects
the per-node attention scores, and hands both to one Graph Engine op that
takes every head's softmax over each node's in-edges and its weighted sum
in one walk of the shard grid (``GraphEngine.edge_softmax_aggregate``).
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
from repro.kernels.ref import _activate
from repro.kernels.registry import KernelBackend, resolve


def build_graph_tensors(edges: np.ndarray, num_nodes: int, n: int,
                        arch: str) -> GraphTensors:
    """Shard + normalize a graph for the given zoo architecture."""
    norm, loops = graph_signature(arch)
    sg = shard_graph(edges, num_nodes, n, normalize=norm,
                     add_self_loops=loops)
    return GraphTensors.from_sharded(sg)


def layer_activation(spec: ZooSpec, i: int) -> str:
    """Activation for layer i: relu between layers (ELU for GAT, as
    published), logits at the end. Shared with the sharded forward
    (dist/gnn.py) so the two execution paths can never disagree on where
    nonlinearities sit."""
    if i == len(spec.layer_dims) - 1:
        return "none"
    return "elu" if spec.arch == "gat" else "relu"


# architectures whose layers are linear up to their activation, so that
# the controller may run either engine first (``linear_layer``)
LINEAR_ARCHS = ("gcn", "sage_mean")


def _controller(plan, backend: KernelBackend | None) -> GNNeratorController:
    b = plan.B if plan is not None else 128
    fused = plan.fused if plan is not None else True
    return GNNeratorController(dense=DenseEngine(backend=backend),
                               graph=GraphEngine(block_b=b, backend=backend),
                               fuse=fused)


def _gat_layer(spec: ZooSpec, layer: dict, gt: GraphTensors, h: jax.Array,
               ctrl: GNNeratorController, *, activation: str,
               last: bool) -> jax.Array:
    """All heads of one GAT layer: z = H W, the scores a·z in float32, and
    one edge softmax aggregation of every head; heads are concatenated,
    or averaged in the output layer."""
    s, n, din = h.shape
    heads, hd = layer["a_src"].shape
    z = ctrl.dense(h.reshape(s * n, din), layer["w"])       # (S·n, H·hd)
    zh = z.reshape(s, n, heads, hd).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    s_src = jnp.einsum("snhf,hf->snh", zh,
                       layer["a_src"].astype(jnp.float32), precision=hi)
    s_dst = jnp.einsum("snhf,hf->snh", zh,
                       layer["a_dst"].astype(jnp.float32), precision=hi)
    out = ctrl.graph.edge_softmax_aggregate(
        gt, z.reshape(s, n, heads * hd), s_src, s_dst,
        negative_slope=spec.negative_slope)                 # (S, n, H·hd)
    if last:
        out = out.reshape(s, n, heads, hd).mean(axis=2)
    return _activate(out, activation)


def _layer(spec: ZooSpec, layer: dict, gt: GraphTensors, h: jax.Array,
           ctrl: GNNeratorController, act: str, last: bool) -> jax.Array:
    """One layer of ``spec.arch``, each stage under the named scope
    (``aggregate``, ``extract``, ``fused`` or ``attention``) that its
    operations carry in the compiled program's metadata."""
    if spec.arch in LINEAR_ARCHS:
        # linear_layer orders and scopes its own stages
        return ctrl.linear_layer(gt, h, layer["w"], activation=act,
                                 concat_self=spec.arch == "sage_mean")
    if spec.arch == "gat":
        with jax.named_scope("attention"):
            return _gat_layer(spec, layer, gt, h, ctrl, activation=act,
                              last=last)
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
            backend: str | KernelBackend | None = None) -> jax.Array:
    """Run the model; h is (S, n, in_dim) shard-grouped (GraphTensors.group).

    ``plans`` is an optional per-layer sequence of LayerPlans from
    repro.gnn.executor; None falls back to the default controller (fused
    where legal, B=128). ``backend`` goes through
    :func:`repro.kernels.registry.resolve` once, for every layer.
    """
    backend = resolve(backend)
    layers = params["layers"]
    for i, layer in enumerate(layers):
        plan = plans[i] if plans is not None else None
        ctrl = _controller(plan, backend)
        act = layer_activation(spec, i)
        with jax.named_scope(f"layer{i}"):
            h = _layer(spec, layer, gt, h, ctrl, act, i == len(layers) - 1)
    return gt.ungroup(h)
