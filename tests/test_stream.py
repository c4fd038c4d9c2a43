"""Streaming graphs: incremental shard patching must be bitwise-equal to
a from-scratch rebuild, mutation must never retrace the forward, the
versioned GraphStore must make stale builds unreachable, invalidation
must stay targeted, and the train-while-serve loop must hold one train
step trace across sampler swaps."""
import numpy as np
import jax
import pytest
from hypothesis import given, settings, strategies as st

from repro import runtime
from repro.gnn.models import ARCHS, ZooSpec, graph_signature
from repro.graphs import GraphDelta, apply_to_graph_data, PatchState
from repro.graphs.datasets import make_dataset
from repro.runtime.api import graph_fingerprint
from repro.serving import Completed, SchedulerConfig, Server
from repro.serving.gnn_engine import GNNServeEngine, NodeRequest
from repro.stream import StreamTrainer, random_delta


def _ds(scale=0.05, seed=0):
    return make_dataset("cora", seed=seed, scale=scale)


def _spec(ds, arch, hidden=8):
    return ZooSpec(arch, ds.profile.feature_dim, hidden,
                   ds.profile.num_classes, num_layers=2, heads=2)


def _peripheral_edge(ds) -> np.ndarray:
    """The (1, 2) edge whose endpoints have the lowest combined degree —
    mutating it keeps the affected k-hop neighborhood small."""
    e = ds.edges
    deg = np.bincount(e.reshape(-1), minlength=ds.profile.num_nodes)
    k = int(np.argmin(deg[e[:, 0]] + deg[e[:, 1]]))
    return e[k:k + 1].copy()


# --------------------------------------------------------------------------
# patcher == rebuild (property test over random delta sequences)
# --------------------------------------------------------------------------

# the four distinct (normalize, self-loops) graph signatures of the zoo
@given(arch=st.sampled_from(["gcn", "sage_mean", "sage_max", "gin"]),
       seed=st.integers(min_value=0, max_value=9999),
       with_nodes=st.booleans())
@settings(max_examples=8, deadline=None)
def test_patch_bitwise_equals_rebuild(arch, seed, with_nodes):
    ds = _ds()
    rng = np.random.default_rng(seed)
    ps = PatchState.for_arch(ds.edges, ds.profile.num_nodes, 32, arch,
                             slack=0.25)
    for _ in range(6):
        delta = random_delta(ds, rng, edge_ops=8,
                             p_node=0.5 if with_nodes else 0.0,
                             max_new_nodes=2)
        apply_to_graph_data(ds, delta)
        ps.apply(delta)
        # element-wise against a from-scratch shard_graph of the SAME
        # post-delta edge list: blocks, COO prefix, slack slots, degrees
        ps.verify_against_rebuild()
        assert ps.num_nodes == ds.profile.num_nodes
        assert ps.edges.shape == ds.edges.shape


# --------------------------------------------------------------------------
# mutation never retraces; patched forward == fresh compile (all archs)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_mutation_forward_parity_no_retrace(arch):
    ds = _ds()
    spec = _spec(ds, arch)
    store = runtime.GraphStore()
    exe = runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                          store=store, graph_key="g", seed=0,
                          mutable_graph=True)
    exe.forward()
    traces = exe._jit_forward._cache_size()

    norm, loops = graph_signature(arch)
    rng = np.random.default_rng(1)
    for _ in range(3):
        delta = random_delta(ds, rng, edge_ops=6)   # edge churn only
        old_v = ds.version
        apply_to_graph_data(ds, delta)
        patched = store.patch("g", delta, old_version=old_v,
                              new_version=ds.version)
        entry, res = patched[(norm, loops, exe.plan.shard_n)]
        assert not res.rebuilt     # slack absorbs these small deltas
        exe.update_graph(entry.gt, entry.h_grouped)

    out = np.asarray(exe.forward())
    # in-template churn must not have added a single jit trace
    assert exe._jit_forward._cache_size() == traces

    fresh = runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                            params=exe.params)
    assert fresh.plan.shard_n == exe.plan.shard_n
    np.testing.assert_array_equal(out, np.asarray(fresh.forward()))


def test_gat_logits_after_a_patch_equal_a_fresh_build():
    """GAT on the pallas backend (its kernel walks the edge tiles): after
    edge churn through GraphStore.patch the patched tiles keep their
    shape, the forward does not retrace, and its logits are a fresh
    compile's of the post-delta graph."""
    ds = _ds()
    spec = _spec(ds, "gat")
    store = runtime.GraphStore()
    exe = runtime.compile(spec, ds, backend="pallas", max_shard_n=64,
                          store=store, graph_key="g", seed=0,
                          mutable_graph=True)
    exe.forward()
    traces = exe._jit_forward._cache_size()
    shapes = [a.shape for a in exe.gt.tiles]
    norm, loops = graph_signature("gat")
    rng = np.random.default_rng(2)
    for _ in range(2):
        delta = random_delta(ds, rng, edge_ops=6)
        old_v = ds.version
        apply_to_graph_data(ds, delta)
        patched = store.patch("g", delta, old_version=old_v,
                              new_version=ds.version)
        entry, res = patched[(norm, loops, exe.plan.shard_n)]
        assert not res.rebuilt
        assert [a.shape for a in entry.gt.tiles] == shapes
        exe.update_graph(entry.gt, entry.h_grouped)
    out = np.asarray(exe.forward())
    assert exe._jit_forward._cache_size() == traces
    fresh = runtime.compile(spec, ds, backend="pallas", max_shard_n=64,
                            params=exe.params, store=runtime.GraphStore())
    np.testing.assert_allclose(out, np.asarray(fresh.forward()),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------------------------------------
# versioned store keys (the in-place-mutation collision bugfix)
# --------------------------------------------------------------------------

def test_fingerprint_folds_version():
    ds = _ds()
    a = graph_fingerprint(ds.edges, ds.profile.num_nodes, ds.features,
                          version=0)
    b = graph_fingerprint(ds.edges, ds.profile.num_nodes, ds.features,
                          version=1)
    # same bytes, bumped version: an in-place delta that misses every
    # strided sample row must still change the cache key
    assert a != b


def test_post_delta_predict_never_serves_stale_logits():
    ds = _ds(scale=0.1)
    spec = _spec(ds, "gcn")
    eng = GNNServeEngine(backend="reference", max_shard_n=64,
                         streaming=True)
    eng.register_graph("g", ds)
    eng.register_model("gcn", spec)
    srv = Server(eng, SchedulerConfig(max_batch_size=4))
    ids = np.arange(ds.profile.num_nodes)

    t = srv.submit(NodeRequest("g", ids, model="gcn"))
    srv.drain()
    before = t.result().value

    # rewire a hub: the served rows around it MUST change
    deg = np.bincount(ds.edges[:, 1], minlength=ds.profile.num_nodes)
    hub = int(np.argmax(deg))
    delta = GraphDelta(del_edges=ds.edges[ds.edges[:, 1] == hub])
    srv.mutate("g", delta)

    t = srv.submit(NodeRequest("g", ids, model="gcn"))
    srv.drain()
    after = t.result().value

    # ground truth: a FRESH compile of the post-delta graph, same weights
    fresh = runtime.compile(spec, ds, backend="reference", max_shard_n=64,
                            params=eng.model_params("gcn"))
    want = np.asarray(jax.nn.softmax(fresh.forward(), axis=-1))
    np.testing.assert_array_equal(after.classes, want.argmax(axis=-1))
    np.testing.assert_allclose(after.probs, want[ids, after.classes],
                               rtol=1e-5, atol=1e-6)
    # the delta visibly changed what's served (no pre-delta leftovers)
    assert not np.allclose(after.probs, before.probs)
    assert srv.metrics()["mutations"] == 1


# --------------------------------------------------------------------------
# targeted invalidation
# --------------------------------------------------------------------------

def test_targeted_invalidation_drops_minority_and_keeps_cache():
    ds = _ds()
    n = ds.profile.num_nodes
    spec = _spec(ds, "gcn")
    eng = GNNServeEngine(backend="reference", max_shard_n=64,
                         streaming=True)
    eng.register_graph("g", ds)
    eng.register_model("gcn", spec)
    srv = Server(eng, SchedulerConfig(max_batch_size=4))

    srv.submit(NodeRequest("g", np.arange(n), model="gcn"))
    srv.drain()                                    # logits cache is warm

    rep = srv.mutate("g", GraphDelta(del_edges=_peripheral_edge(ds)))
    (m,) = rep["executables"]
    assert m["targeted"] and not m["recompile"]
    assert 0 < m["rows_invalidated"] < 0.5 * n     # minority dropped
    assert m["rows_cached"] == n
    s = eng.stats
    assert s["targeted_invalidations"] == 1 and s["full_invalidations"] == 0
    assert s["nodes_invalidated"] == m["rows_invalidated"]
    assert s["graph_recompiles"] == 0

    # fresh rows still serve straight from the cache...
    exe = eng._executables[("gcn", "g")]
    stale_mask = np.asarray(exe._stale)
    fresh_ids = np.flatnonzero(~stale_mask)[:4]
    assert exe.probs_fresh_for(fresh_ids)
    # ...and stale rows do not
    assert not exe.probs_fresh_for(np.flatnonzero(stale_mask)[:1])


def test_full_invalidation_mode_drops_everything():
    ds = _ds()
    n = ds.profile.num_nodes
    eng = GNNServeEngine(backend="reference", max_shard_n=64,
                         streaming=True, invalidation="full")
    eng.register_graph("g", ds)
    eng.register_model("gcn", _spec(ds, "gcn"))
    srv = Server(eng, SchedulerConfig(max_batch_size=4))
    srv.submit(NodeRequest("g", np.arange(n), model="gcn"))
    srv.drain()
    rep = srv.mutate("g", GraphDelta(del_edges=_peripheral_edge(ds)))
    (m,) = rep["executables"]
    assert not m["targeted"] and m["rows_invalidated"] == n
    assert eng.stats["full_invalidations"] == 1


# --------------------------------------------------------------------------
# mutation retrace oracle (repro.analyze) + known-bad unpadded fixture
# --------------------------------------------------------------------------

def _insert_into_fullest_pair(ps) -> GraphDelta:
    """An edge insert aimed at the shard pair with the least headroom —
    the first insert a slack=0 template cannot absorb."""
    i, j = np.unravel_index(np.argmax(ps.counts), ps.counts.shape)
    have = {(int(u), int(v)) for u, v in ps.edges}
    for u in range(j * ps.n, min((j + 1) * ps.n, ps.num_nodes)):
        for v in range(i * ps.n, min((i + 1) * ps.n, ps.num_nodes)):
            if u != v and (u, v) not in have:
                return GraphDelta(add_edges=np.array([[u, v]]))
    raise AssertionError("fullest shard pair is completely dense")


def test_mutation_oracle_clean_on_slack_padded_template():
    from repro.analyze import jaxpr_lint

    ds = _ds()
    exe = runtime.compile(_spec(ds, "gcn"), ds, backend="reference",
                          max_shard_n=64, mutable_graph=True,
                          store=runtime.GraphStore())
    rng = np.random.default_rng(0)
    deltas = [random_delta(ds, rng, edge_ops=4) for _ in range(4)]
    findings = jaxpr_lint.check_mutation_stability(
        exe, ds.edges, ds.profile.num_nodes, deltas)
    assert findings == []


def test_mutation_oracle_flags_unpadded_template():
    """Known-bad fixture: slack=0 leaves zero headroom, so one insert
    into the fullest shard pair compacts — a template break the RT003
    mutation oracle must flag as an error."""
    from repro.analyze import jaxpr_lint

    ds = _ds()
    # fresh store: the cache key ignores edge_slack (a capacity hint, not
    # content), so the default store could serve a slack-padded build here
    exe = runtime.compile(_spec(ds, "gcn"), ds, backend="reference",
                          max_shard_n=64, mutable_graph=True,
                          edge_slack=0.0, store=runtime.GraphStore())
    norm, loops = graph_signature("gcn")
    probe = PatchState(ds.edges, ds.profile.num_nodes, exe.gt.n,
                       normalize=norm, add_self_loops=loops,
                       edge_capacity=int(exe.gt.edge_src.shape[-1]))
    delta = _insert_into_fullest_pair(probe)
    findings = jaxpr_lint.check_mutation_stability(
        exe, ds.edges, ds.profile.num_nodes, [delta])
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "RT003" and f.severity == "error"
    assert "broke the graph template" in f.message


# --------------------------------------------------------------------------
# train-while-serve
# --------------------------------------------------------------------------

def test_stream_trainer_one_step_trace_across_rounds():
    ds = _ds(scale=0.1)
    eng = GNNServeEngine(backend="reference", max_shard_n=64,
                         streaming=True)
    eng.register_graph("g", ds)
    eng.register_model("gcn", _spec(ds, "gcn"))
    srv = Server(eng, SchedulerConfig(max_batch_size=4))
    trainer = StreamTrainer(srv, graph="g", model="gcn", batch_nodes=16,
                            fanout=(4, 4), steps_per_round=3,
                            seed=0, log=lambda s: None)

    rng = np.random.default_rng(2)
    for _ in range(2):
        srv.mutate("g", random_delta(ds, rng, edge_ops=6))
        rep = trainer.round()
        assert not rep["skipped"] and rep["dirty_nodes"] > 0
        # the jitted train step must survive the sampler swap untraced
        assert rep["step_traces"] == 1

    assert trainer.stats == {
        "rounds": 2, "rounds_skipped": 0, "steps": 6, "reloads": 2,
        "rebuilds": 0, "step_traces": 1}
    # nothing mutated since the last round: the trainer skips
    assert trainer.round()["skipped"]
    assert trainer.stats["rounds_skipped"] == 1
    assert 0.0 <= trainer.train_accuracy() <= 1.0

    # the hot-reloaded weights actually serve: engine params == trained
    served = eng.model_params("gcn")
    trained = trainer._trainable.params
    leaves_a = jax.tree_util.tree_leaves(served)
    leaves_b = jax.tree_util.tree_leaves(trained)
    for a, b in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_server_mutate_requires_gnn_engine():
    from repro.configs.registry import get_smoke
    from repro.models import lm
    from repro.serving.engine import ServeEngine

    cfg = get_smoke("qwen3-8b")
    eng = ServeEngine(cfg, lm.init_params(cfg, jax.random.key(0)),
                      max_len=32)
    srv = Server(eng, SchedulerConfig(max_batch_size=2))
    with pytest.raises(TypeError):
        srv.mutate("g", GraphDelta())


def test_serving_continues_through_mutation_burst():
    """End-to-end: interleave requests and deltas; every ticket completes
    and the forward never recompiles."""
    ds = _ds()
    eng = GNNServeEngine(backend="reference", max_shard_n=64,
                         streaming=True)
    eng.register_graph("g", ds)
    eng.register_model("gcn", _spec(ds, "gcn"))
    srv = Server(eng, SchedulerConfig(max_batch_size=4))
    rng = np.random.default_rng(3)

    tickets = []
    for _ in range(5):
        ids = rng.integers(0, ds.profile.num_nodes, size=6)
        tickets.append(srv.submit(NodeRequest("g", ids, model="gcn")))
        srv.drain()
        srv.mutate("g", random_delta(ds, rng, edge_ops=4))
    srv.drain()
    assert all(isinstance(t.result(), Completed) for t in tickets)
    assert eng.stats["graph_recompiles"] == 0
    assert eng.stats["mutations"] == 5
    exe = eng._executables[("gcn", "g")]
    assert exe._jit_forward._cache_size() == 1
