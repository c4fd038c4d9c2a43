"""The shard grid's device dtype: bfloat16 where every consumer rounds it
to bfloat16 anyway (a TPU at the default matmul precision), float32
elsewhere; and every consumer of a bfloat16 grid computes what it
computes on the same grid upcast to float32."""
import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import runtime
from repro.core.engines import upload_grid
from repro.core.sharding import shard_graph
from repro.gnn.models import ZooSpec
from repro.graphs.patch import PatchState
from repro.kernels import registry

BF16, F32 = jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)
NUM, N = 40, 16


@pytest.mark.parametrize("platform,precision,want", [
    ("cpu", None, F32),
    ("cpu", "highest", F32),
    ("tpu", None, BF16),
    ("tpu", "default", BF16),
    ("tpu", "bfloat16", BF16),
    ("tpu", "highest", F32),
    ("tpu", "float32", F32),
    ("tpu", "tensorfloat32", F32),
    ("gpu", None, F32),
])
def test_grid_dtype_rule(platform, precision, want):
    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        # the precision the rule reads where the grid is uploaded
        assert jax.config.jax_default_matmul_precision == precision
        assert registry.grid_dtype(
            platform, jax.config.jax_default_matmul_precision) == want


def test_the_grid_stays_float32_here():
    """The tier-1 suite runs on the CPU, where the dots are exact float32."""
    assert registry.device_grid_dtype() == F32
    rng = np.random.default_rng(0)
    sg = shard_graph(rng.integers(0, NUM, (120, 2)), NUM, N,
                     normalize="gcn", add_self_loops=True)
    assert upload_grid(sg.blocks).dtype == F32


def _graph(rng, normalize="gcn"):
    sg = shard_graph(rng.integers(0, NUM, (120, 2)), NUM, N,
                     normalize=normalize, add_self_loops=True)
    return jnp.asarray(sg.blocks.astype(BF16))


def _bf16_exact(rng, shape):
    """Features that bfloat16 holds exactly: what the MXU's default pass
    makes of any float32 operand."""
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x.astype(BF16).astype(np.float32))


@pytest.mark.parametrize("consumer", ["shard_spmm", "fused_gnn",
                                      "edge_softmax_aggregate",
                                      "oracle_vjp"])
def test_a_bfloat16_grid_computes_what_its_float32_upcast_does(consumer):
    rng = np.random.default_rng(1)
    be = registry.get_backend("pallas")
    blocks = _graph(rng)
    s = blocks.shape[0]
    h = _bf16_exact(rng, (s, N, 24))
    w = _bf16_exact(rng, (24, 5))
    if consumer == "shard_spmm":
        def call(a):
            return be.graph_aggregate(a, h)
    elif consumer == "fused_gnn":
        def call(a):
            return be.fused_aggregate_extract(a, h, w, activation="relu")
    elif consumer == "edge_softmax_aggregate":
        heads = 4
        z = h[..., :heads * 6]
        s_src = jnp.asarray(rng.standard_normal((s, N, heads)), jnp.float32)
        s_dst = jnp.asarray(rng.standard_normal((s, N, heads)), jnp.float32)

        def call(a):
            return be.edge_softmax_aggregate(a, z, s_src, s_dst,
                                             heads=heads)
    else:
        def call(a):
            # both kinds of layer backward, the aggregation's and the
            # fused layer's, each through the oracle the custom VJP
            # differentiates
            def loss(h, w):
                agg = be.graph_aggregate(a, h)
                out = be.fused_aggregate_extract(a, h, w,
                                                 activation="relu")
                return jnp.sum(agg * agg) + jnp.sum(out * out)
            return jax.grad(loss, argnums=(0, 1))(h, w)

    got = call(blocks)
    want = call(blocks.astype(F32))
    for g, wv in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == wv.dtype == F32
        np.testing.assert_array_equal(np.asarray(g), np.asarray(wv))


def test_a_bfloat16_grid_keeps_the_edge_mask():
    """Every weight the grid holds rounds to a nonzero bfloat16, so the
    edge softmax sees the same edges: 0/1, 1/deg, 1/sqrt(deg·deg)."""
    rng = np.random.default_rng(2)
    for norm in ("gcn", "mean", "sum"):
        sg = shard_graph(rng.integers(0, NUM, (120, 2)), NUM, N,
                         normalize=norm, add_self_loops=True)
        np.testing.assert_array_equal(sg.blocks.astype(BF16) != 0,
                                      sg.blocks != 0)


def test_patching_a_bfloat16_grid_keeps_its_dtype(monkeypatch):
    """A TPU's upload, here: the patch's targeted update of a bfloat16
    device grid stays bfloat16 and equals a fresh upload of the rebuilt
    graph, and the host's float32 copy still matches its rebuild."""
    monkeypatch.setattr(registry, "device_grid_dtype", lambda: BF16)
    rng = np.random.default_rng(3)
    edges = rng.integers(0, NUM, (120, 2))
    ps = PatchState(edges, NUM, N, normalize="gcn", add_self_loops=True)
    gt = ps.to_graph_tensors()
    assert gt.blocks.dtype == BF16
    from repro.graphs.delta import GraphDelta
    res = ps.apply(GraphDelta(add_edges=np.array([[0, 17], [33, 2]]),
                              del_edges=edges[:3]))
    assert not res.rebuilt and res.pairs[0].size > 0
    patched = ps.to_graph_tensors(prev=gt, pairs=res.pairs)
    ps.verify_against_rebuild()
    fresh = shard_graph(ps.edges, NUM, N, normalize="gcn",
                        add_self_loops=True)
    assert patched.blocks.dtype == BF16
    np.testing.assert_array_equal(np.asarray(patched.blocks),
                                  np.asarray(upload_grid(fresh.blocks)))


def test_the_store_builds_again_for_another_grid_dtype(monkeypatch):
    """A build asked for under another matmul precision than its grid was
    stored for is not handed out: a compile of the float32 oracle after
    a bfloat16 compile gets a float32 grid of its own."""
    rng = np.random.default_rng(4)
    edges = rng.integers(0, NUM, (120, 2))
    feats = rng.standard_normal((NUM, 8)).astype(np.float32)
    spec = ZooSpec("gcn", 8, 8, 3)
    store = runtime.GraphStore()
    with monkeypatch.context() as m:
        m.setattr(registry, "device_grid_dtype", lambda: BF16)
        exe = runtime.compile(spec, (edges, NUM, feats), backend="reference",
                              max_shard_n=N, store=store)
    oracle = runtime.compile(spec, (edges, NUM, feats), backend="reference",
                             max_shard_n=N, store=store)
    assert exe.gt.blocks.dtype == BF16 and oracle.gt.blocks.dtype == F32
    assert store.stats["misses"] == 2 and len(store) == 1
    again = runtime.compile(spec, (edges, NUM, feats), backend="reference",
                            max_shard_n=N, store=store)
    assert again.gt is oracle.gt and store.stats["hits"] == 1
