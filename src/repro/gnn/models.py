"""GNN model zoo on the GNNerator engines (VersaGNN-style coverage).

Every architecture is assembled from the same two engines the paper builds
in silicon — the Dense Engine (blocked systolic matmul + activation unit)
and the Graph Engine (shard-grid aggregation with dimension blocking) —
composed by the GNNeratorController. Per layer, an executor-provided
:class:`repro.gnn.executor.LayerPlan` picks the feature block size B and
whether the two stages run fused (h_agg never leaves VMEM) or two-stage
through feature memory.

Architectures (all multi-layer, relu between layers, logits at the end;
GAT has ELU between its layers, as published):

  gcn        H' = act(Â H W)                       either order, fusable
  sage_mean  H' = act(W [mean_N∪u(H); H])          either order
  sage_max   z = relu(H W_p + b_p); z̄ = max_N z;
             H' = act(W [z̄; H])                    dense-first (pool)
  gin        H' = MLP((1+ε) H + Σ_N H)             graph-first, ε learnable
  gat        H' = elu(‖_heads Σ_u α_vu z_u)        z = H W, then one edge
             logits = mean_heads Σ_u α_vu z_u      softmax aggregation of
                                                   all heads per layer

GAT's attention runs in one Graph Engine op per layer
(``GraphEngine.edge_softmax_aggregate``): every head's softmax over each
node's in-edges and its weighted sum, in one walk of the graph's edge
tiles (the source columns of each row tile that hold an edge), so the
weights α never exist as a grid of their own. Hidden layers
concatenate ``heads`` heads; the output layer averages ``out_heads``.

"Either order": the layer is linear up to its activation, so the
controller runs it dense-first (aggregating after extraction) where that
walks the shard grid fewer times, graph-first otherwise
(``GNNeratorController.linear_layer``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

ARCHS = ("gcn", "sage_mean", "sage_max", "gin", "gat")

# arch -> (edge-weight normalization baked into the shard blocks,
#          add self loops when sharding)
_GRAPH_SIG = {
    "gcn": ("gcn", True),
    "sage_mean": ("mean", True),
    "sage_max": ("sum", True),    # gather path; binary blocks — shares the
                                  # cached GraphTensors with gat
    "gin": ("sum", False),        # (1+ε)·h term replaces the self loop
    "gat": ("sum", True),         # binary mask; α supplies the weights
}


# architectures whose layers run the edge softmax aggregation, and so
# need their graph's edge tiles (``GraphTensors.with_edge_tiles``)
EDGE_SOFTMAX_ARCHS = ("gat",)


def graph_signature(arch: str) -> tuple[str, bool]:
    """(normalize, add_self_loops) a model needs its GraphTensors built with.

    Serving keys its graph-tensor cache on exactly this signature: two
    models with the same signature share one sharded graph (GNNIE-style
    graph-specific caching).
    """
    return _GRAPH_SIG[arch]


@dataclasses.dataclass(frozen=True)
class ZooSpec:
    arch: str
    in_dim: int
    hidden_dim: int
    out_dim: int
    num_layers: int = 2
    heads: int = 2                 # GAT hidden layers, concatenated
    out_heads: int = 1             # GAT output layer, averaged
    eps_init: float = 0.0          # GIN ε initial value (learnable)
    negative_slope: float = 0.2    # GAT LeakyReLU

    def __post_init__(self):
        if self.arch not in ARCHS:
            raise ValueError(f"unknown arch {self.arch!r}; choose {ARCHS}")
        if self.num_layers < 1:
            raise ValueError("need at least one layer")
        if self.arch == "gat" and self.hidden_dim % self.heads:
            raise ValueError("gat: hidden_dim must divide by heads")
        if self.heads < 1 or self.out_heads < 1:
            raise ValueError("need at least one head")

    @property
    def layer_dims(self) -> list[tuple[int, int]]:
        dims = ([self.in_dim] + [self.hidden_dim] * (self.num_layers - 1)
                + [self.out_dim])
        return list(zip(dims[:-1], dims[1:]))

    def agg_dim(self, layer: int) -> int:
        """Feature dim live at aggregation time (what the planner blocks)."""
        din, dout = self.layer_dims[layer]
        if self.arch == "gat":
            # aggregation runs over z = h W (all heads; the output layer's
            # out_heads are averaged after it)
            last = layer == len(self.layer_dims) - 1
            return dout * self.out_heads if last else dout
        return din   # gcn/sage_mean/gin aggregate h; sage_max pools at din


def _glorot(key, shape):
    fan_in, fan_out = shape[0], shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(key, shape, dtype=jnp.float32) * scale


def init_zoo(key: jax.Array, spec: ZooSpec) -> dict:
    """Param pytree: {"layers": [per-layer dict]}."""
    layers = []
    for i, (din, dout) in enumerate(spec.layer_dims):
        key, k1, k2, k3 = jax.random.split(key, 4)
        if spec.arch == "gcn":
            layer = {"w": _glorot(k1, (din, dout))}
        elif spec.arch == "sage_mean":
            layer = {"w": _glorot(k1, (2 * din, dout))}
        elif spec.arch == "sage_max":
            layer = {"w_pool": _glorot(k1, (din, din)),
                     "b_pool": jnp.zeros((din,), jnp.float32),
                     "w": _glorot(k2, (2 * din, dout))}
        elif spec.arch == "gin":
            layer = {"eps": jnp.float32(spec.eps_init),
                     "w1": _glorot(k1, (din, dout)),
                     "b1": jnp.zeros((dout,), jnp.float32),
                     "w2": _glorot(k2, (dout, dout)),
                     "b2": jnp.zeros((dout,), jnp.float32)}
        elif spec.arch == "gat":
            if i < spec.num_layers - 1:
                heads, hd = spec.heads, dout // spec.heads
            else:
                heads, hd = spec.out_heads, dout
            layer = {"w": _glorot(k1, (din, heads * hd)),
                     "a_src": _glorot(k2, (heads, hd)),
                     "a_dst": _glorot(k3, (heads, hd))}
        layers.append(layer)
    return {"layers": layers}
