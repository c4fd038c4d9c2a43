"""Compile the Pallas kernels for a described TPU v5e, no chip attached.

Interpret mode accepts tilings and VMEM footprints that Mosaic refuses;
compiling for ``v5e:2x2`` here catches those refusals before a chip run.
Each kernel is compiled with ``interpret=False`` at the shapes the pallas
backend hands it for full cora and full pubmed gcn: the planner's shard
size n and grid S, and its feature block B after the backend's lane
legalization (``registry._feature_block``).

The topology is described inside a module-scoped fixture, never while
this file is imported: only one process may hold the TPU library, and
under several test workers only the worker running this file may load it.
"""
import functools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import runtime
from repro.analyze import plan_lint
from repro.core.engines import GraphTensors
from repro.core.sharding import shard_graph
from repro.gnn.executor import plan_model
from repro.gnn.models import ZooSpec, graph_signature, init_zoo
from repro.graphs.datasets import make_dataset
from repro.kernels import (dense_engine, edge_softmax, fused_gnn, seg_gather,
                           shard_spmm)
from repro.kernels import registry
from repro.kernels.registry import _feature_block
from repro.runtime.fit import TrainableExecutable

GRAPHS = ("cora", "pubmed")


@pytest.fixture(scope="module")
def topo():
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # entries compiled for a described chip cannot be read back without
    # one; keep the persistent cache out of these compiles
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        desc = None
        reason = f"no v5e:2x2 topology can be described here: {e}"
    try:
        if desc is None:
            pytest.skip(reason)
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", saved_cache)
        if saved_log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = saved_log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def graphs():
    """Full-size datasets + their gcn plans (hidden 16, 2 layers)."""
    out = {}
    for name in GRAPHS:
        ds = make_dataset(name, seed=0)
        prof = ds.profile
        spec = ZooSpec("gcn", prof.feature_dim, 16, prof.num_classes,
                       num_layers=2)
        out[name] = (ds, spec, plan_model(spec, prof.num_nodes,
                                          int(ds.edges.shape[0])))
    return out


def _compile(fn, *avals):
    compiled = jax.jit(fn).lower(*avals).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _layer_shapes(graphs, name, layer):
    """(S, n, padded d, kernel B, out dim) of one gcn layer."""
    _, spec, plan = graphs[name]
    lp = plan.layers[layer]
    bb, dp = _feature_block(lp.d_agg, lp.B)
    return lp.S, plan.shard_n, dp, bb, spec.layer_dims[layer][1]


def _max_pair_edges(ds, n: int) -> int:
    """Largest per-shard-pair edge count (self loops included) — the E of
    the padded (S, S, E) edge lists the gather kernel walks."""
    num = ds.profile.num_nodes
    loops = np.arange(num)
    src = np.concatenate([ds.edges[:, 0], loops])
    dst = np.concatenate([ds.edges[:, 1], loops])
    s = -(-num // n)
    counts = np.zeros((s, s), np.int64)
    np.add.at(counts, (dst // n, src // n), 1)
    return int(counts.max())


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("name", GRAPHS)
def test_shard_spmm_compiles(graphs, one_chip, name, layer):
    s, n, dp, bb, _ = _layer_shapes(graphs, name, layer)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    _compile(functools.partial(shard_spmm.shard_spmm, block_b=bb,
                               interpret=False),
             f32((s, s, n, n)), f32((s, n, dp)))


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("name", GRAPHS)
def test_fused_gnn_layer_compiles(graphs, one_chip, name, layer):
    s, n, dp, bb, f = _layer_shapes(graphs, name, layer)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    _compile(functools.partial(fused_gnn.fused_gnn_layer, block_b=bb,
                               activation="relu", interpret=False),
             f32((s, s, n, n)), f32((s, n, dp)), f32((dp, f)))


@pytest.mark.parametrize("width", ["extract", "pool"])
@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("name", GRAPHS)
def test_dense_engine_matmul_compiles(graphs, one_chip, name, layer, width):
    """The layer's Dense Engine matmul with bias, tiled as the pallas
    backend tiles it: the feature extraction (d -> f) run two-stage, and
    sage_max's pooling transform at the layer's width (d -> d)."""
    s, n, _, _, f = _layer_shapes(graphs, name, layer)
    d = graphs[name][1].layer_dims[layer][0]
    out = f if width == "extract" else d
    bk, kp = _feature_block(d, 128)
    bn, np_ = _feature_block(out, 128)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    _compile(functools.partial(dense_engine.dense_engine_matmul,
                               activation="relu", bm=128, bn=bn, bk=bk,
                               interpret=False),
             f32((s * n, kp)), f32((kp, np_)), f32((np_,)))


@pytest.mark.parametrize("width", [16, 3])
def test_shard_spmm_compiles_dense_first(graphs, one_chip, width):
    """The aggregation of a dense-first layer at full pubmed shapes: GCN
    layer 0's 16 extracted features, SAGE-mean layer 1's 3 (padded to
    8), each one full-width block."""
    s, n = graphs["pubmed"][2].layers[0].S, graphs["pubmed"][2].shard_n
    bb, dp = _feature_block(width, 16)
    assert bb == dp
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    _compile(functools.partial(shard_spmm.shard_spmm, block_b=bb,
                               interpret=False),
             f32((s, s, n, n)), f32((s, n, dp)))


@pytest.mark.parametrize("k,m", [(500, 16), (256, 6)])
def test_dense_engine_compiles_dense_first(one_chip, k, m):
    """The extraction of a dense-first layer over full pubmed's 20480
    padded rows, tiled as the pallas backend tiles it, with no bias: GCN
    layer 0 (500 -> 16), and SAGE-mean layer 1 (256 -> 3 + 3, against
    [W_top | W_bot])."""
    bk, kp = _feature_block(k, 128)
    bn, np_ = _feature_block(m, 128)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    _compile(functools.partial(dense_engine.dense_engine_matmul, bm=128,
                               bn=bn, bk=bk, interpret=False),
             f32((20480, kp)), f32((kp, np_)))


@pytest.mark.parametrize("layer", [0, 1])
@pytest.mark.parametrize("name", GRAPHS)
def test_seg_gather_aggregate_compiles(graphs, one_chip, name, layer):
    s, n, dp, bb, _ = _layer_shapes(graphs, name, layer)
    e = _max_pair_edges(graphs[name][0], n)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    _compile(functools.partial(seg_gather.seg_gather_aggregate, op="max",
                               block_b=bb, interpret=False),
             i32((s, s, e)), i32((s, s, e)),
             jax.ShapeDtypeStruct((s, s, e), jnp.bool_, sharding=one_chip),
             jax.ShapeDtypeStruct((s, n, dp), jnp.float32,
                                  sharding=one_chip))


def _edge_tiles(graphs, name, one_chip):
    """(S, n, tile avals) of a graph's edge tiles, in the tile rule's
    shape and sized for its edges and self loops."""
    ds, _, plan = graphs[name]
    s, n = plan.layers[0].S, plan.shard_n
    rows, width = edge_softmax.tile_shape(n)
    t = edge_softmax.chunk_bound(
        s * n, ds.edges.shape[0] + ds.profile.num_nodes, rows, width)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    i32 = functools.partial(sds, dtype=jnp.int32)
    return s, n, edge_softmax.EdgeTiles(
        i32((t, width)), sds((t, rows, width), jnp.bfloat16), i32((t,)),
        i32((t,)), i32((t,)), i32((1,)))


@pytest.mark.parametrize("heads,f", [(8, 8), (8, 3)])
def test_edge_softmax_aggregate_compiles(graphs, one_chip, heads, f):
    """GAT's attention at full pubmed shapes: layer 0's 8 heads of 8 and
    the output layer's 8 heads of 3, over the edge tiles of the 20 x 20
    grid of 1024."""
    s, n, tiles = _edge_tiles(graphs, "pubmed", one_chip)
    assert (s, n) == (20, 1024)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    _compile(functools.partial(edge_softmax.edge_softmax_aggregate,
                               heads=heads, negative_slope=0.2,
                               interpret=False),
             tiles, f32((s, n, heads * f)), f32((s, n, heads)),
             f32((s, n, heads)))


def test_gat_forward_holds_no_grid_but_the_adjacency(one_chip, monkeypatch):
    """GAT at its PubMed widths (500 -> 8 x 8 -> 8 x 3 averaged) through
    runtime.compile on the pallas backend: the forward compiled for v5e
    runs only the edge softmax and dense kernels, and no (S, S, n, n)
    array other than the adjacency it is given."""
    monkeypatch.setattr(registry, "_interpret", lambda: False)
    r = np.random.default_rng(0)
    # n = 1024, as on full pubmed: a grid of a few MB would be prefetched
    # into VMEM whole, which copies the adjacency but computes nothing
    num, d = 2100, 500
    edges = r.integers(0, num, (8400, 2))
    feats = r.standard_normal((num, d)).astype(np.float32)
    spec = ZooSpec("gat", d, 64, 3, heads=8, out_heads=8)
    exe = runtime.compile(spec, (edges, num, feats), backend="pallas",
                          max_shard_n=1024, store=runtime.GraphStore())
    s, n = exe.gt.S, exe.gt.n
    assert (s, n) == (3, 1024)
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                       sharding=one_chip),
        (exe.params, exe._h_grouped, exe._graph_args()))
    p, h, ga = avals
    compiled = exe._jit_forward.lower(p, h, *ga).compile()
    names = _custom_call_names(compiled)
    assert set(names) == {edge_softmax.KERNEL_NAME,
                          dense_engine.KERNEL_NAME}, names
    assert names.count(edge_softmax.KERNEL_NAME) == 2
    # each instruction's result type: the text between "=" and its opcode
    inst = re.compile(r"\s*%([\w.\-]+) = (.*?) [a-z][\w\-]*\(")
    held = [m.group(1) for m in map(inst.match,
                                    compiled.as_text().splitlines())
            if m and f"[{s},{s},{n},{n}]" in m.group(2)]
    assert len(held) == 1 and held[0].startswith("blocks"), held


def test_fused_over_vmem_is_refused_and_flagged(graphs, one_chip):
    """n=2048, B=128 overflows the fused kernel's VMEM: the compiler
    refuses it, and the plan pass (PL003) flags the same plan."""
    _, _, plan = graphs["pubmed"]
    lp = plan.layers[0]
    n, b = 2048, 128
    big = plan_lint.LayerPlan(**{**lp.to_json(), "n": n, "B": b,
                                 "S": -(-plan.num_nodes // n),
                                 "fused": True})
    rules = {f.rule for f in plan_lint.check_layer(plan, big,
                                                   backend_name="pallas")}
    assert "PL003" in rules
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    with pytest.raises(Exception, match="vmem"):
        _compile(functools.partial(fused_gnn.fused_gnn_layer, block_b=b,
                                   interpret=False),
                 f32((1, 1, n, n)), f32((1, n, b)), f32((b, 16)))


def _custom_call_names(compiled) -> list[str]:
    """The instruction names of the program's Pallas calls, their
    ``.N`` suffixes stripped."""
    text = compiled.as_text()
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", text)
    return [re.sub(r"\.\d+$", "", n) for n in names]


def _train_step_compiled(graphs, one_chip, monkeypatch):
    """The GCN full-batch train step (value_and_grad through the kernels'
    custom VJPs, AdamW) compiled at full cora shapes: layer 0 (1433 ->
    16) runs dense-first, layer 1 the fused kernel."""
    monkeypatch.setattr(registry, "_interpret", lambda: False)
    ds, spec, _ = graphs["cora"]
    exe = runtime.compile(spec, ds, backend="pallas",
                          store=runtime.GraphStore())
    te = TrainableExecutable(exe, ds.labels)
    avals = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), x.dtype,
                                       sharding=one_chip),
        (te.params, te.opt_state, te.data(0)))
    p, s, batch = avals
    return te._jit_step.lower(p, s, *batch).compile()


@pytest.mark.parametrize("kernel", ["shard_spmm", "fused_gnn",
                                    "dense_engine", "seg_gather",
                                    "edge_softmax", "train_step"])
def test_pallas_calls_carry_kernel_names(graphs, one_chip, monkeypatch,
                                         kernel):
    """Each Pallas call's instruction is named after its kernel's
    constant, whatever jitted function it sits in: the profiler's trace
    shows the instruction name."""
    s, n, dp, bb, f = _layer_shapes(graphs, "cora", 0)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    if kernel == "shard_spmm":
        compiled = _compile(functools.partial(
            shard_spmm.shard_spmm, block_b=bb, interpret=False),
            f32((s, s, n, n)), f32((s, n, dp)))
        want = {shard_spmm.KERNEL_NAME}
    elif kernel == "fused_gnn":
        compiled = _compile(functools.partial(
            fused_gnn.fused_gnn_layer, block_b=bb, interpret=False),
            f32((s, s, n, n)), f32((s, n, dp)), f32((dp, f)))
        want = {fused_gnn.KERNEL_NAME}
    elif kernel == "dense_engine":
        compiled = _compile(functools.partial(
            dense_engine.dense_engine_matmul, activation="relu", bm=128,
            bn=128, bk=bb, interpret=False),
            f32((s * n, dp)), f32((dp, 128)), f32((128,)))
        want = {dense_engine.KERNEL_NAME}
    elif kernel == "seg_gather":
        e = _max_pair_edges(graphs["cora"][0], n)
        i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                                sharding=one_chip)
        compiled = _compile(functools.partial(
            seg_gather.seg_gather_aggregate, op="max", block_b=bb,
            interpret=False),
            i32((s, s, e)), i32((s, s, e)),
            jax.ShapeDtypeStruct((s, s, e), jnp.bool_, sharding=one_chip),
            f32((s, n, dp)))
        want = {seg_gather.KERNEL_NAME}
    elif kernel == "edge_softmax":
        s, n, tiles = _edge_tiles(graphs, "cora", one_chip)
        compiled = _compile(functools.partial(
            edge_softmax.edge_softmax_aggregate, heads=8,
            negative_slope=0.2, interpret=False),
            tiles, f32((s, n, 64)), f32((s, n, 8)), f32((s, n, 8)))
        want = {edge_softmax.KERNEL_NAME}
    else:
        compiled = _train_step_compiled(graphs, one_chip, monkeypatch)
        want = {fused_gnn.KERNEL_NAME, shard_spmm.KERNEL_NAME,
                dense_engine.KERNEL_NAME}
    names = _custom_call_names(compiled)
    assert names and set(names) == want, names


@pytest.mark.parametrize("kernel", ["shard_spmm", "fused_gnn",
                                    "edge_softmax"])
def test_kernels_compile_on_a_bfloat16_grid(graphs, one_chip, kernel):
    """The grid a TPU stores at the default matmul precision, at full
    pubmed shapes: each kernel takes its bfloat16 tiles (the edge
    softmax those of its edge tiles' mask, which it compares with 0 on
    v5e, whose VPU has no bfloat16 arithmetic)."""
    s, n, dp, bb, f = _layer_shapes(graphs, "pubmed", 0)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one_chip)
    grid = jax.ShapeDtypeStruct((s, s, n, n), jnp.bfloat16,
                                sharding=one_chip)
    if kernel == "shard_spmm":
        _compile(functools.partial(shard_spmm.shard_spmm, block_b=bb,
                                   interpret=False), grid, f32((s, n, dp)))
    elif kernel == "fused_gnn":
        _compile(functools.partial(fused_gnn.fused_gnn_layer, block_b=bb,
                                   activation="relu", interpret=False),
                 grid, f32((s, n, dp)), f32((dp, f)))
    else:
        _, _, tiles = _edge_tiles(graphs, "pubmed", one_chip)
        assert tiles.mask.dtype == grid.dtype
        _compile(functools.partial(edge_softmax.edge_softmax_aggregate,
                                   heads=8, negative_slope=0.2,
                                   interpret=False),
                 tiles, f32((s, n, 64)), f32((s, n, 8)), f32((s, n, 8)))


def _entry_grid_instructions(text: str, s: int, n: int) -> list[tuple]:
    """(name, opcode) of each instruction of the ENTRY computation whose
    result holds an (s, s, n, n) array: what the program materializes."""
    lines = text.splitlines()
    start = next(i for i, ln in enumerate(lines) if ln.startswith("ENTRY"))
    inst = re.compile(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")
    return [(m.group(1), m.group(3)) for m in map(inst.match, lines[start:])
            if m and f"[{s},{s},{n},{n}]" in m.group(2)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gcn_gradient_reads_the_grid_in_its_stored_dtype(graphs, one_chip,
                                                         monkeypatch, dtype):
    """The GCN gradient (value_and_grad through the pallas backend's
    oracle VJPs) at full pubmed shapes: five instructions read the grid
    (the two Pallas kernels of the forward and three fusions of the XLA
    backward), all in the dtype it is stored in, and the program holds
    no copy or conversion of it."""
    monkeypatch.setattr(registry, "_interpret", lambda: False)
    ds, spec, plan = graphs["pubmed"]
    s, n = plan.layers[0].S, plan.shard_n
    e = 64
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params = jax.eval_shape(
        lambda: init_zoo(jax.random.key(0), spec))
    p = jax.tree.map(lambda x: sds(x.shape, x.dtype), params)
    be = registry.get_backend("pallas")

    def loss(p, h, blocks, e_src, e_dst, e_valid):
        gt = GraphTensors(blocks=blocks, edge_src=e_src, edge_dst=e_dst,
                          edge_valid=e_valid, num_nodes=s * n, n=n, S=s)
        out = runtime.forward(spec, p, gt, h, plans=plan.layers, backend=be)
        return jnp.sum(out * out)

    compiled = jax.jit(jax.value_and_grad(loss)).lower(
        p, sds((s, n, spec.layer_dims[0][0]), jnp.float32),
        sds((s, s, n, n), jnp.dtype(dtype)), sds((s, s, e), jnp.int32),
        sds((s, s, e), jnp.int32), sds((s, s, e), jnp.bool_)).compile()
    text = compiled.as_text()
    held = _entry_grid_instructions(text, s, n)
    assert [op for _, op in held] == ["parameter"], held
    grid = held[0][0]
    short = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    assert f"{short}[{s},{s},{n},{n}]" in text
    readers = [ln for ln in text.splitlines()
               if re.search(rf"\((%{re.escape(grid)}|[^)]*, %"
                            rf"{re.escape(grid)})[,)]", ln)]
    kinds = sorted("tpu_custom_call" if "tpu_custom_call" in ln else "fusion"
                   for ln in readers)
    assert kinds == ["fusion"] * 3 + ["tpu_custom_call"] * 2, readers


@pytest.mark.parametrize("arch", ["gcn", "sage_mean", "gat"])
def test_a_bfloat16_grid_gives_the_float32_grids_logits_on_the_chip(arch):
    """On a TPU at the default matmul precision (skipped elsewhere): the
    compiled forward on the bfloat16 grid it uploads and on the float32
    host grid agree to float32 accumulation order, since every consumer
    rounded the float32 grid to bfloat16 already."""
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU")
    r = np.random.default_rng(0)
    num, d = 600, 64
    edges = r.integers(0, num, (2400, 2))
    feats = r.standard_normal((num, d)).astype(np.float32)
    kw = dict(heads=4, out_heads=2) if arch == "gat" else {}
    spec = ZooSpec(arch, d, 32, 3, **kw)
    exe = runtime.compile(spec, (edges, num, feats), backend="pallas",
                          max_shard_n=128, store=runtime.GraphStore())
    blocks, *rest = exe._graph_args()
    assert blocks.dtype == jnp.bfloat16
    norm, loops = graph_signature(arch)
    host = shard_graph(edges, num, exe.gt.n, normalize=norm,
                       add_self_loops=loops).blocks
    out = np.asarray(exe._jit_forward(exe.params, exe._h_grouped, blocks,
                                      *rest))
    ref = np.asarray(exe._jit_forward(exe.params, exe._h_grouped,
                                      jnp.asarray(host), *rest))
    scale = np.abs(ref).max()
    assert scale > 0
    assert np.abs(out - ref).max() <= 1e-5 * scale
