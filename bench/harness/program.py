"""The system under test, built the way its users build it: the one place
the drivers turn a configuration into the program's own objects."""
from __future__ import annotations


def zoo_spec(cfg: dict):
    from repro.gnn.models import ZooSpec
    g = cfg["graph"]
    return ZooSpec(cfg["arch"], g["feature_dim"], cfg["hidden_dim"],
                   g["num_classes"], num_layers=cfg["num_layers"])


def compile_program(ctx):
    """``runtime.compile`` of the configuration on the benchmark's graph and
    weights, with a graph store of its own (freed with the program)."""
    from repro import runtime
    cfg, g = ctx.cell.config, ctx.graph
    return runtime.compile(
        zoo_spec(cfg), (g.edges, g.num_nodes, g.features),
        backend=cfg["backend"], plan=cfg["plan"], params=ctx.params,
        max_shard_n=cfg["shard_n"], store=runtime.GraphStore())
