"""The system under test, built the way its users build it: the one place
the drivers turn a configuration into the program's own objects."""
from __future__ import annotations


def zoo_spec(cfg: dict):
    """The program's ``ZooSpec`` of a configuration: its arch, widths and
    depth, and every key of its ``spec`` object as a keyword argument. A
    key ``ZooSpec`` does not have raises ``TypeError``; none is dropped,
    so a configuration never runs with a model key other than it states."""
    from repro.gnn.models import ZooSpec
    g = cfg["graph"]
    return ZooSpec(cfg["arch"], g["feature_dim"], cfg["hidden_dim"],
                   g["num_classes"], num_layers=cfg["num_layers"],
                   **cfg.get("spec", {}))


def compile_program(ctx):
    """``runtime.compile`` of the configuration on the benchmark's graph and
    weights, with a graph store of its own (freed with the program)."""
    from repro import runtime
    cfg, g = ctx.cell.config, ctx.graph
    return runtime.compile(
        zoo_spec(cfg), (g.edges, g.num_nodes, g.features),
        backend=cfg["backend"], plan=cfg["plan"], params=ctx.params,
        max_shard_n=cfg["shard_n"], store=runtime.GraphStore())
