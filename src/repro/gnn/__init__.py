"""GNN model zoo specs + layer execution planning (``repro.gnn``).

``models``   — ZooSpec / init for multi-layer GCN / GraphSAGE(mean,max) /
               GIN / GAT (forward execution lives in ``repro.runtime``).
``executor`` — per-layer (S, B, order, fused?) planning via the Table-I
               cost model in core/dataflow.py + core/perf_model.py,
               content-hash memoized with JSON round-tripping.
"""
from repro.gnn.executor import (LayerPlan, ModelPlan, clear_plan_cache,
                                plan_cache_stats, plan_model)
from repro.gnn.models import ARCHS, ZooSpec, graph_signature, init_zoo

__all__ = [
    "LayerPlan", "ModelPlan", "clear_plan_cache", "plan_cache_stats",
    "plan_model", "ARCHS", "ZooSpec", "graph_signature", "init_zoo",
]
