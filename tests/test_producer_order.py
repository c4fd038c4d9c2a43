"""The controller's producer order for layers linear up to their activation.

GCN and GraphSAGE-mean layers give the same result as ``(A·H)·W`` and
``A·(H·W)``; ``GNNeratorController.linear_layer`` runs the Dense Engine
first where that walks the dense shard grid strictly fewer times, counted
with the kernels' own lane rounding. The rule is checked at the
benchmark's PubMed shapes, and the two orders must agree on the logits and
on the gradients through the kernels' custom VJPs.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.core.engines import GNNeratorController, GraphEngine
from repro.gnn.executor import plan_model
from repro.gnn.models import ZooSpec, init_zoo
from repro.kernels import registry
from repro.runtime.fit import masked_cross_entropy
from repro.runtime.forward import (build_graph_tensors, forward,
                                   producer_orders)

PUBMED = (19717, 88648)


@pytest.mark.parametrize("din,dout,block_b,order,walks", [
    (500, 16, 16, "dense-first", 1),     # GCN L0: 4 walks graph-first
    (16, 3, 16, "graph-first", 1),       # GCN L1: a tie keeps the fused kernel
    (500, 256, 500, "graph-first", 1),   # SAGE-mean L0: 504 wide, one walk
    (256, 3, 16, "dense-first", 1),      # SAGE-mean L1: 2 walks graph-first
    (16, 256, 16, "graph-first", 1),     # widening layer
    (200, 500, 16, "graph-first", 2),    # widening, several walks each way
], ids=["gcn-l0", "gcn-l1", "sage-l0", "sage-l1", "widen", "widen-wide"])
def test_order_rule(din, dout, block_b, order, walks):
    ctrl = GNNeratorController(graph=GraphEngine(block_b=block_b))
    assert ctrl.producer_order(din, dout) == (order, walks)


@pytest.mark.parametrize("arch,hidden,want", [
    ("gcn", 16, [("dense-first", 1), ("graph-first fused", 1)]),
    ("sage_mean", 256, [("graph-first", 1), ("dense-first", 1)]),
])
def test_orders_at_benchmark_plans(arch, hidden, want):
    """The planner's PubMed plans (B = 16, 16 and 500, 16) run two grid
    walks a forward, down from 5 (GCN) and 3 (SAGE-mean)."""
    spec = ZooSpec(arch, 500, hidden, 3, num_layers=2)
    plan = plan_model(spec, *PUBMED, max_n=1024)
    assert producer_orders(spec, plan.layers) == want
    assert sum(w for _, w in want) == 2


@pytest.mark.parametrize("arch", ["gin", "gat", "sage_max"])
def test_fixed_order_archs_report_none(arch):
    spec = ZooSpec(arch, 500, 16, 3, num_layers=2)
    assert producer_orders(spec) == []


N, S_N = 40, 16
DIN, HID, CLASSES = 256, 16, 3


def _setup(arch):
    rng = np.random.default_rng(5)
    edges = rng.integers(0, N, (160, 2)).astype(np.int64)
    gt = build_graph_tensors(edges, N, S_N, arch)
    spec = ZooSpec(arch, DIN, HID, CLASSES, num_layers=2)
    plan = plan_model(spec, N, edges.shape[0], max_n=S_N)
    h = gt.group(jnp.asarray(rng.standard_normal((N, DIN)), jnp.float32))
    labels = jnp.asarray(rng.integers(0, CLASSES, N), jnp.int32)
    mask = jnp.asarray(rng.random(N) < 0.6)
    return spec, plan, gt, h, labels, mask


@pytest.mark.parametrize("arch", ["gcn", "sage_mean"])
def test_dense_first_matches_graph_first(arch):
    """Layer 0 (256 -> 16) runs dense-first at B=16 (two 128-lane walks
    graph-first, one after extraction) and graph-first at B=256: logits
    and gradients through the pallas kernels' custom VJPs agree."""
    spec, plan, gt, h, labels, mask = _setup(arch)
    pallas = registry.get_backend("pallas")
    runs = {}
    for b in (16, 256):
        plans = (dataclasses.replace(plan.layers[0], B=b),) + \
            plan.layers[1:]
        order = producer_orders(spec, plans)[0][0]

        def loss(p, plans=plans):
            logits = forward(spec, p, gt, h, plans=plans, backend=pallas)
            return masked_cross_entropy(logits, labels, mask), logits

        params = init_zoo(jax.random.key(3), spec)
        (_, logits), grads = jax.value_and_grad(loss, has_aux=True)(params)
        runs[order.split()[0]] = (logits, grads)
    assert set(runs) == {"dense-first", "graph-first"}
    (lg_d, g_d), (lg_g, g_g) = runs["dense-first"], runs["graph-first"]
    np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_g),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_d), jax.tree.leaves(g_g)):
        assert float(jnp.max(jnp.abs(b))) > 0
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_dense_first_runs_extraction_before_aggregation():
    """Dense-first GCN drops the fused kernel for the Dense Engine
    followed by the shard-grid SpMM; graph-first keeps it."""
    spec, plan, gt, h, _, _ = _setup("gcn")
    pallas = registry.get_backend("pallas")
    params = init_zoo(jax.random.key(3), spec)
    names = {}
    for b in (16, 256):
        plans = (dataclasses.replace(plan.layers[0], B=b),) + \
            plan.layers[1:]
        text = str(jax.make_jaxpr(
            lambda p: forward(spec, p, gt, h, plans=plans,
                              backend=pallas))(params))
        names[b] = [k for k in ("gnn_fused_aggregate_extract",
                                "gnn_shard_spmm", "gnn_dense_engine")
                    if k in text]
    assert names[256] == ["gnn_fused_aggregate_extract"]
    assert names[16] == ["gnn_fused_aggregate_extract", "gnn_shard_spmm",
                         "gnn_dense_engine"]


def test_summary_shows_orders_and_walks():
    rng = np.random.default_rng(2)
    edges = rng.integers(0, N, (120, 2)).astype(np.int64)
    feats = rng.standard_normal((N, 300)).astype(np.float32)
    spec = ZooSpec("gcn", 300, 16, 3, num_layers=2)
    exe = runtime.compile(spec, (edges, N, feats), backend="reference",
                          max_shard_n=S_N, store=runtime.GraphStore())
    want = producer_orders(spec, exe.plan.layers)
    assert exe.producer_orders() == want
    assert want[0] == ("dense-first", 1)
    line = exe.summary().splitlines()[-1]
    assert line.startswith("  producer order: L0 dense-first 1 walk, L1 ")
    assert line.endswith(f"; {sum(w for _, w in want)} grid walks per "
                         f"forward")
