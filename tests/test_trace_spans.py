"""The program's host spans in the profiler's trace: every span the
runtime, the server and the graph patch open appears, nests inside its
parent, and is opened again by a second call (none runs only while jit
traces); the jitted programs carry their names and their layers' scopes.
"""
import glob
import math
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData

from repro import runtime
from repro.gnn.models import ZooSpec
from repro.graphs import GraphDelta
from repro.graphs.datasets import make_dataset
from repro.runtime.fit import TrainableExecutable
from repro.serving import SchedulerConfig, Server
from repro.serving.gnn_engine import GNNServeEngine, NodeRequest

# child -> the span it runs inside
PARENT = {
    "gnn.compile.plan": "gnn.compile",
    "gnn.compile.shard": "gnn.compile",
    "gnn.compile.upload": "gnn.compile",
    "gnn.forward.slice": "gnn.forward",
    "serve.schedule": "serve.step",
    "serve.engine": "serve.step",
    "serve.softmax": "serve.engine",
    "graph.apply_delta": "graph.mutate",
    "graph.patch": "graph.mutate",
    "graph.upload": "graph.patch",
    "graph.invalidate": "graph.mutate",
}
SPANS = set(PARENT) | set(PARENT.values()) | {"gnn.train_step"}
PREFIXES = ("gnn.", "serve.", "graph.")


def _host_spans(logdir) -> list[tuple[str, int, int, dict]]:
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = int(ev.start_ns)
                    out.append((ev.name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    """Two calls of each entry point, all under one profiler session."""
    ds = make_dataset("cora", seed=0, scale=0.1)
    spec = ZooSpec("gcn", ds.profile.feature_dim, 8, ds.profile.num_classes,
                   num_layers=2)
    eng = GNNServeEngine(backend="reference", max_shard_n=64,
                         streaming=True)
    eng.register_graph("g", ds)
    eng.register_model("gcn", spec)
    srv = Server(eng, SchedulerConfig(max_batch_size=4))
    ids = np.arange(ds.profile.num_nodes)
    gat = ZooSpec("gat", ds.profile.feature_dim, 16, ds.profile.num_classes,
                  heads=8, out_heads=2)
    logdir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(logdir):
        runtime.compile(gat, ds, backend="reference", max_shard_n=64,
                        store=runtime.GraphStore())
        for _ in range(2):
            exe = runtime.compile(spec, ds, backend="reference",
                                  max_shard_n=64, store=runtime.GraphStore())
            jax.block_until_ready(exe.forward())
        te = TrainableExecutable(exe, ds.labels)
        p, s = te.params, te.opt_state
        for _ in range(2):
            p, s, m = te.step_fn(p, s, te.data(0))
        jax.block_until_ready(m["loss"])
        for k in range(2):
            # every row requested after a mutation: a fresh softmax
            srv.submit(NodeRequest("g", ids, model="gcn"))
            srv.drain()
            e = ds.edges[k:k + 1]
            srv.mutate("g", GraphDelta(del_edges=e))
        srv.submit(NodeRequest("g", ids, model="gcn"))
        srv.drain()
    return _host_spans(logdir)


def test_every_span_appears_twice(spans):
    counts = {name: sum(1 for n, *_ in spans if n == name) for name in SPANS}
    assert all(c >= 2 for c in counts.values()), counts


def test_children_lie_inside_their_parents(spans):
    for child in spans:
        parent = PARENT.get(child[0])
        if parent is None:
            continue
        assert any(_inside(child, p) for p in spans if p[0] == parent), \
            child


def test_serve_step_names_its_batch(spans):
    steps = [st for n, _, _, st in spans if n == "serve.step" and st]
    assert steps and all(st["size"] >= 1 for st in steps)
    batches = [st["batch"] for st in steps]
    assert batches == sorted(batches) and len(set(batches)) == len(batches)


def test_compile_counts_its_dense_first_layers(spans):
    """cora's 1433 features take 12 walks of the grid graph-first and one
    once extracted to 8: every compile of the gcn runs layer 0 dense-first
    and layer 1 graph-first."""
    counts = [st.get("dense_first_layers") for n, _, _, st in spans
              if n == "gnn.compile" and not st.get("edge_softmax_layers")]
    assert len(counts) >= 2 and all(c == 1 for c in counts), counts


def test_compile_counts_its_edge_softmax_layers(spans):
    """Both layers of the one GAT compiled run the edge softmax
    aggregation, and no layer of any gcn does."""
    counts = [st.get("edge_softmax_layers") for n, _, _, st in spans
              if n == "gnn.compile"]
    assert counts.count(2) == 1 and set(counts) == {0, 2}, counts


def test_compile_names_its_grid_dtype_and_bytes(spans):
    """Every compile here, on the CPU, keeps the (S, S, n, n) grid in
    float32: S·n is a multiple of the shard size 64, and the bytes are
    4 a cell."""
    grids = [(st.get("grid_dtype"), st.get("grid_bytes"))
             for n, _, _, st in spans if n == "gnn.compile"]
    assert len(grids) >= 3, grids
    for dtype, nbytes in grids:
        side = math.isqrt(nbytes // 4)
        assert dtype == "float32" and side * side * 4 == nbytes, grids
        assert side % 64 == 0, grids


def test_gat_forward_carries_its_kernels_and_attention_scopes():
    ds = make_dataset("cora", seed=0, scale=0.05)
    spec = ZooSpec("gat", ds.profile.feature_dim, 16,
                   ds.profile.num_classes, heads=8, out_heads=2)
    exe = runtime.compile(spec, ds, backend="pallas", max_shard_n=64,
                          store=runtime.GraphStore())
    text = exe._jit_forward.lower(exe.params, exe._h_grouped,
                                  *exe._graph_args()).as_text(
                                      debug_info=True)
    for name in ("gnn_edge_softmax_aggregate", "gnn_dense_engine",
                 "layer0/attention", "layer1/attention"):
        assert name in text, name


def test_jitted_programs_carry_names_and_scopes():
    ds = make_dataset("cora", seed=0, scale=0.05)
    spec = ZooSpec("sage_mean", ds.profile.feature_dim, 8,
                   ds.profile.num_classes, num_layers=2)
    exe = runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                          store=runtime.GraphStore())
    text = exe._jit_forward.lower(exe.params, exe._h_grouped,
                                  *exe._graph_args()).as_text(
                                      debug_info=True)
    assert "@jit_gnn_forward" in text
    for scope in ("layer0/aggregate", "layer0/extract", "layer1/aggregate",
                  "layer1/extract"):
        assert scope in text, scope
    te = TrainableExecutable(exe, ds.labels)
    text = te._jit_step.lower(te.params, te.opt_state,
                              *te.data(0)).as_text(debug_info=True)
    assert "@jit_gnn_train_step" in text
    for scope in ("jvp(loss)", "adamw/", "transpose(jvp(layer0))"):
        assert scope in text, scope
    assert exe._jit_gather.__name__ == "gnn_node_gather"
