"""Dense Engine / Graph Engine abstractions (paper §III).

On the ASIC these are two physical compute engines coordinated by the
GNNerator Controller (either may be producer or consumer). In the JAX/TPU
port they are thin, configurable wrappers over the Pallas kernels; the
Controller's role — deciding the producer/consumer order and whether the
two stages can be fine-grain pipelined — becomes a kernel-selection
decision: graph-first layers with linear aggregation use the *fused*
kernel (h_agg never leaves VMEM), everything else composes the two engine
kernels through HBM exactly like the ASIC's feature memory. A layer that
is linear up to its activation may run either way round; the controller
makes the Dense Engine the producer where that walks the dense shard grid
fewer times (``GNNeratorController.linear_layer``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.sharding import ShardedGraph
from repro.kernels import registry
from repro.kernels.ref import _activate
from repro.kernels.registry import KernelBackend, grid_walks


@functools.partial(jax.jit, donate_argnums=0)
def _cast_row_into(grid: jax.Array, row: jax.Array, i) -> jax.Array:
    """``grid[i] = row`` in the grid's dtype, in place."""
    return jax.lax.dynamic_update_index_in_dim(
        grid, row.astype(grid.dtype), i, 0)


def upload_grid(blocks: np.ndarray) -> jax.Array:
    """The host's float32 shard grid on the device, in the dtype that
    ``registry.device_grid_dtype()`` gives where it is uploaded.

    Another dtype is cast on the device, one destination row of shard
    pairs at a time into a grid allocated once: the host makes no pass
    over the grid (a host cast of full PubMed's took about 1 s on a v5e
    host), and the device holds no float32 copy of more than one row."""
    dtype = registry.device_grid_dtype()
    if blocks.dtype == dtype:
        return jnp.asarray(blocks)
    grid = jnp.zeros(blocks.shape, dtype)
    for i in range(blocks.shape[0]):
        grid = jax.block_until_ready(_cast_row_into(grid, blocks[i], i))
    return grid


@dataclasses.dataclass(frozen=True)
class GraphTensors:
    """Device-ready arrays for one sharded graph + one normalization."""

    blocks: jax.Array      # (S, S, n, n) densified adjacency (normalized)
    edge_src: jax.Array    # (S, S, E) int32
    edge_dst: jax.Array    # (S, S, E) int32
    edge_valid: jax.Array  # (S, S, E) bool
    num_nodes: int
    n: int
    S: int

    @classmethod
    def from_sharded(cls, sg: ShardedGraph) -> "GraphTensors":
        return cls(
            blocks=upload_grid(sg.blocks),
            edge_src=jnp.asarray(sg.edge_src),
            edge_dst=jnp.asarray(sg.edge_dst),
            edge_valid=jnp.asarray(sg.edge_valid),
            num_nodes=sg.num_nodes,
            n=sg.n,
            S=sg.S,
        )

    @property
    def occupancy(self):
        """(S, S) edge count per shard (numpy) — lets graphs/partition.py
        plan over cached GraphTensors exactly like a ShardedGraph."""
        return np.asarray(self.edge_valid.sum(axis=-1))

    def group(self, h: jax.Array) -> jax.Array:
        """(N, D) node features -> (S, n, D) shard-grouped (zero padded)."""
        d = h.shape[-1]
        pad = self.S * self.n - h.shape[0]
        h = jnp.pad(h, ((0, pad), (0, 0)))
        return h.reshape(self.S, self.n, d)

    def ungroup(self, h: jax.Array) -> jax.Array:
        """(S, n, D) -> (N, D)."""
        d = h.shape[-1]
        return h.reshape(self.S * self.n, d)[: self.num_nodes]


@dataclasses.dataclass(frozen=True)
class DenseEngine:
    """Feature extraction: blocked systolic matmul + activation unit.

    ``backend`` is the :class:`~repro.kernels.registry.KernelBackend` it
    calls (``runtime.forward.forward`` resolves it once per model); an
    engine left at None only plans and runs no kernel."""

    bm: int = 128
    bn: int = 128
    bk: int = 128
    backend: KernelBackend | None = None

    def __call__(self, x, w, b=None, *, activation: str = "none"):
        return self.backend.dense_matmul(x, w, b, activation=activation,
                                         bm=self.bm, bn=self.bn, bk=self.bk)


@dataclasses.dataclass(frozen=True)
class GraphEngine:
    """Aggregation over the shard grid with dimension-blocking, on the
    ``backend`` it holds (as :class:`DenseEngine`)."""

    block_b: int = 128   # the paper's B (feature block size)
    backend: KernelBackend | None = None

    def aggregate(self, gt: GraphTensors, h: jax.Array, *,
                  op: Literal["linear", "max", "sum"] = "linear") -> jax.Array:
        """h: (S, n, D) shard-grouped. Linear = weights baked into blocks
        (sum/mean/gcn); max/sum go through the edge-list gather kernel."""
        if op == "linear":
            return self.backend.graph_aggregate(gt.blocks, h,
                                                block_b=self.block_b)
        return self.backend.gather_aggregate(
            gt.edge_src, gt.edge_dst, gt.edge_valid, h, op=op,
            block_b=self.block_b)

    def edge_softmax_aggregate(self, gt: GraphTensors, z: jax.Array,
                               s_src: jax.Array, s_dst: jax.Array, *,
                               negative_slope: float) -> jax.Array:
        """Attention-weighted aggregation of every head (GAT) in one op:
        z (S, n, H·F) head-major, s_src/s_dst (S, n, H) per-node scores.
        The weights are a softmax over each node's in-edges on the binary
        blocks, so they never exist as a grid of their own."""
        return self.backend.edge_softmax_aggregate(
            gt.blocks, z, s_src, s_dst, heads=s_src.shape[-1],
            negative_slope=negative_slope)


@dataclasses.dataclass(frozen=True)
class GNNeratorController:
    """Composes the engines per layer topology (paper §III-C).

    graph-first + linear aggregation -> fused kernel (fine-grain pipeline);
    otherwise the stages run back-to-back through feature memory.
    """

    dense: DenseEngine = DenseEngine()
    graph: GraphEngine = GraphEngine()
    fuse: bool = True

    def graph_first(self, gt: GraphTensors, h: jax.Array, w: jax.Array,
                    b=None, *, activation: str = "none") -> jax.Array:
        """act((A · H) · W) — GCN-style layer body on grouped features."""
        if self.fuse and b is None:
            with jax.named_scope("fused"):
                return self.graph.backend.fused_aggregate_extract(
                    gt.blocks, h, w, activation=activation,
                    block_b=self.graph.block_b)
        with jax.named_scope("aggregate"):
            agg = self.graph.aggregate(gt, h, op="linear")
        s, n, d = agg.shape
        with jax.named_scope("extract"):
            out = self.dense(agg.reshape(s * n, d), w, b,
                             activation=activation)
        return out.reshape(s, n, -1)

    def producer_order(self, din: int, dout: int) -> tuple[str, int]:
        """``(order, walks)`` of a linear layer from ``din`` to ``dout``
        features: ``"dense-first"`` where aggregating the ``dout``
        extracted features walks the shard grid strictly fewer times than
        aggregating the ``din`` inputs, else ``"graph-first"``; ``walks``
        counts the chosen order's full reads of the grid."""
        first = grid_walks(din, self.graph.block_b)
        after = grid_walks(dout, self.graph.block_b)
        if after < first:
            return "dense-first", after
        return "graph-first", first

    def linear_layer(self, gt: GraphTensors, h: jax.Array, w: jax.Array, *,
                     activation: str = "none",
                     concat_self: bool = False) -> jax.Array:
        """A layer linear up to its activation, in ``producer_order``.

        ``concat_self=False``: act(A · H · W) (GCN), W (din, dout).
        ``concat_self=True``: act([A · H ; H] · W) (GraphSAGE-mean),
        W (2·din, dout). Graph-first aggregates H and extracts after (the
        fused kernel for GCN, where the controller fuses). Dense-first
        extracts first and aggregates ``dout`` features: A · (H · W), and
        A · (H · W_top) + H · W_bot from one dense call against
        [W_top | W_bot]. A tie keeps graph-first, whose fused intermediate
        never leaves VMEM.
        """
        s, n, din = h.shape
        dout = w.shape[-1]
        order, _ = self.producer_order(din, dout)
        if order == "graph-first":
            if not concat_self:
                # graph_first scopes its own stages
                return self.graph_first(gt, h, w, activation=activation)
            with jax.named_scope("aggregate"):
                agg = self.graph.aggregate(gt, h, op="linear")
            with jax.named_scope("extract"):
                cat = jnp.concatenate([agg, h], axis=-1)
                return self.dense(cat.reshape(s * n, 2 * din), w,
                                  activation=activation).reshape(s, n, dout)
        with jax.named_scope("extract"):
            if concat_self:
                w = jnp.concatenate([w[:din], w[din:]], axis=1)
            z = self.dense(h.reshape(s * n, din), w).reshape(s, n, -1)
        with jax.named_scope("aggregate"):
            out = self.graph.aggregate(gt, z[..., :dout], op="linear")
            if concat_self:
                out = out + z[..., dout:]
            return _activate(out, activation)
