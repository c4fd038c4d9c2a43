"""repro.dist — the distribution layer: sharding rules, mesh compat,
HLO collective accounting and multi-device sharded GNN execution.

Three pieces:

  * :mod:`repro.dist.shardings` — logical-axis -> mesh-axis rules with
    divisibility / axis-reuse / missing-axis guards (LM dry-run + train).
  * :mod:`repro.dist.hlo_analysis` — parse compiled HLO text into
    per-collective operand/wire byte counts (the dry-run's traffic model
    and the sharded Executable's comm verification).
  * :mod:`repro.dist.gnn` — ``runtime.compile(spec, graph, mesh=...)``
    support: a :class:`ShardedExecutable` whose forward runs under
    ``shard_map`` (data axis = contiguous dst-shard row groups, model
    axis = feature blocks).

:mod:`repro.dist.compat` builds meshes with explicit ``AxisType.Auto``
axes.
"""
from repro.dist.compat import abstract_mesh, make_mesh
from repro.dist.hlo_analysis import (CollectiveStats, analyze_collectives,
                                     type_bytes)
from repro.dist.shardings import ShardingRules

__all__ = [
    "ShardingRules", "CollectiveStats", "analyze_collectives", "type_bytes",
    "abstract_mesh", "make_mesh",
]
