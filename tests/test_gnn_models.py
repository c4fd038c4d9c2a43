"""repro.gnn subsystem: model zoo vs pure-jnp references, executor budget
invariants, and the batched serving engine's caching behavior."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.gnn.executor import plan_model
from repro.gnn.models import ARCHS, ZooSpec, init_zoo
from repro.graphs.datasets import DATASETS, load, make_dataset
from repro.kernels import ref
from repro.runtime.forward import build_graph_tensors, forward
from repro.serving.gnn_engine import GNNServeEngine, NodeRequest


@pytest.fixture(autouse=True)
def _ref_backend(monkeypatch):
    """Model-level tests target assembly logic (grouping, normalization,
    attention, planning), not kernel numerics — kernel parity is covered by
    tests/test_kernels.py. The jnp backend keeps the sweep fast on CPU."""
    monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")


def _flat_adj(gt) -> np.ndarray:
    b = np.asarray(gt.blocks)
    s, _, n, _ = b.shape
    return b.transpose(0, 2, 1, 3).reshape(s * n, s * n)


def _ref_forward(arch, layers, a, h):
    """The published layers: relu between layers, except GAT's ELU; GAT's
    hidden heads concatenated, its output heads averaged."""
    n_layers = len(layers)
    for i, L in enumerate(layers):
        last = i == n_layers - 1
        act = "none" if last else ("elu" if arch == "gat" else "relu")
        if arch == "gcn":
            h = ref.gcn_layer(a, h, L["w"], activation=act)
        elif arch == "sage_mean":
            h = ref.sage_mean_layer(a, h, L["w"], activation=act)
        elif arch == "sage_max":
            h = ref.sage_max_pool_layer(a, h, L["w_pool"], L["b_pool"],
                                        L["w"], activation=act)
        elif arch == "gin":
            h = ref.gin_layer(a, h, L["eps"], L["w1"], L["b1"], L["w2"],
                              L["b2"], activation=act)
        elif arch == "gat":
            h = ref.gat_layer(a, h, L["w"], L["a_src"], L["a_dst"],
                              activation=act, concat_heads=not last)
    return h


class TestZooVsReference:
    """Every zoo model through the engine path must match the flat pure-jnp
    oracle on (scaled) Cora/Citeseer profiles within fp32 tolerance —
    including multi-shard grids (max_n forces S > 1)."""

    @pytest.mark.parametrize("arch", ARCHS)
    @pytest.mark.parametrize("dataset", ["cora", "citeseer"])
    def test_model_matches_reference(self, arch, dataset):
        ds = make_dataset(dataset, seed=1, scale=0.08)
        prof = ds.profile
        spec = ZooSpec(arch, prof.feature_dim, 8, prof.num_classes,
                       num_layers=2, heads=2)
        mp = plan_model(spec, prof.num_nodes, ds.edges.shape[0], max_n=64)
        assert mp.layers[0].S > 1, "test must exercise a multi-shard grid"
        gt = build_graph_tensors(ds.edges, prof.num_nodes, mp.shard_n, arch)
        params = init_zoo(jax.random.key(0), spec)
        out = forward(spec, params, gt, gt.group(jnp.asarray(ds.features)),
                      plans=mp.layers)

        a = _flat_adj(gt)
        h = np.zeros((a.shape[0], prof.feature_dim), np.float32)
        h[:prof.num_nodes] = ds.features
        exp = np.asarray(_ref_forward(arch, params["layers"], a,
                                      jnp.asarray(h)))[:prof.num_nodes]
        np.testing.assert_allclose(np.asarray(out), exp,
                                   atol=5e-5, rtol=5e-5)

    def test_three_layer_gcn(self):
        ds = make_dataset("cora", seed=2, scale=0.05)
        prof = ds.profile
        spec = ZooSpec("gcn", prof.feature_dim, 8, prof.num_classes,
                       num_layers=3)
        mp = plan_model(spec, prof.num_nodes, ds.edges.shape[0], max_n=32)
        gt = build_graph_tensors(ds.edges, prof.num_nodes, mp.shard_n, "gcn")
        params = init_zoo(jax.random.key(1), spec)
        out = forward(spec, params, gt, gt.group(jnp.asarray(ds.features)),
                      plans=mp.layers)
        a = _flat_adj(gt)
        h = np.zeros((a.shape[0], prof.feature_dim), np.float32)
        h[:prof.num_nodes] = ds.features
        exp = np.asarray(_ref_forward("gcn", params["layers"], a,
                                      jnp.asarray(h)))[:prof.num_nodes]
        np.testing.assert_allclose(np.asarray(out), exp, atol=5e-5, rtol=5e-5)

    @pytest.mark.parametrize("out_heads", [1, 8])
    def test_gat_output_heads_are_averaged(self, out_heads):
        """GAT as published for PubMed: 8 hidden heads of 8, ELU, and
        ``out_heads`` output heads averaged; multi-shard grid. Both sides
        compute in float32 on the CPU, so only summation order differs."""
        ds = make_dataset("citeseer", seed=4, scale=0.08)
        prof = ds.profile
        spec = ZooSpec("gat", prof.feature_dim, 64, prof.num_classes,
                       num_layers=2, heads=8, out_heads=out_heads)
        mp = plan_model(spec, prof.num_nodes, ds.edges.shape[0], max_n=64)
        assert mp.layers[0].S > 1
        assert mp.layers[1].d_agg == out_heads * prof.num_classes
        gt = build_graph_tensors(ds.edges, prof.num_nodes, mp.shard_n, "gat")
        params = init_zoo(jax.random.key(5), spec)
        last = params["layers"][1]
        assert last["w"].shape == (64, out_heads * prof.num_classes)
        assert last["a_src"].shape == (out_heads, prof.num_classes)
        out = forward(spec, params, gt, gt.group(jnp.asarray(ds.features)),
                      plans=mp.layers)
        a = _flat_adj(gt)
        h = np.zeros((a.shape[0], prof.feature_dim), np.float32)
        h[:prof.num_nodes] = ds.features
        exp = np.asarray(_ref_forward("gat", params["layers"], a,
                                      jnp.asarray(h)))[:prof.num_nodes]
        np.testing.assert_allclose(np.asarray(out), exp, atol=5e-5, rtol=5e-5)

    def test_pallas_interpret_parity(self, monkeypatch):
        """One small end-to-end run through the real kernel path (interpret
        mode on CPU) to pin the engine wiring, not just the ref backend."""
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "pallas")
        r = np.random.default_rng(0)
        n_nodes, d, c = 40, 16, 4
        e = r.integers(0, n_nodes, (160, 2))
        e = e[e[:, 0] != e[:, 1]]
        feats = r.standard_normal((n_nodes, d)).astype(np.float32)
        for arch in ("gcn", "gat"):
            spec = ZooSpec(arch, d, 8, c, num_layers=2, heads=2)
            mp = plan_model(spec, n_nodes, len(e), max_n=16)
            gt = build_graph_tensors(e, n_nodes, mp.shard_n, arch)
            params = init_zoo(jax.random.key(0), spec)
            out = forward(spec, params, gt, gt.group(jnp.asarray(feats)),
                          plans=mp.layers)
            a = _flat_adj(gt)
            h = np.zeros((a.shape[0], d), np.float32)
            h[:n_nodes] = feats
            exp = np.asarray(_ref_forward(arch, params["layers"], a,
                                          jnp.asarray(h)))[:n_nodes]
            np.testing.assert_allclose(np.asarray(out), exp,
                                       atol=1e-4, rtol=1e-4)


def test_load_helper_matches_make_dataset():
    """load() is the one-call (features, labels, edges) contract."""
    f, y, e = load("cora", seed=3, scale=0.05)
    ds = make_dataset("cora", seed=3, scale=0.05)
    np.testing.assert_array_equal(f, ds.features)
    np.testing.assert_array_equal(y, ds.labels)
    np.testing.assert_array_equal(e, ds.edges)
    assert f.shape[0] == y.shape[0] == ds.profile.num_nodes
    assert e.ndim == 2 and e.shape[1] == 2


class TestExecutor:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_plans_fit_onchip_budget(self, arch):
        """Planner invariant: src block + dst accumulators + adjacency
        block, double-buffered, never exceed the platform budget."""
        prof = DATASETS["cora"]
        spec = ZooSpec(arch, prof.feature_dim, 16, prof.num_classes,
                       num_layers=3, heads=2)
        mp = plan_model(spec, prof.num_nodes, prof.num_edges)
        assert len(mp.layers) == 3
        for p in mp.layers:
            assert p.onchip_bytes_used() <= mp.onchip_bytes // 2
            assert 1 <= p.B <= p.d_agg
            assert p.S == -(-mp.num_nodes // p.n)
            assert p.est_layer_s > 0
        # the single execution shard size keeps EVERY layer under budget
        for p in mp.layers:
            used = (2 * mp.shard_n * p.B + mp.shard_n ** 2) * 4
            assert used <= mp.onchip_bytes // 2

    def test_blocking_chosen_for_wide_features(self):
        """Cora's 1433-dim input layer must be dimension-blocked (B < D):
        the whole point of the paper's dataflow."""
        prof = DATASETS["cora"]
        spec = ZooSpec("gcn", prof.feature_dim, 16, prof.num_classes)
        mp = plan_model(spec, prof.num_nodes, prof.num_edges)
        assert mp.layers[0].B < prof.feature_dim

    def test_only_gcn_fuses(self):
        prof = DATASETS["citeseer"]
        for arch in ARCHS:
            spec = ZooSpec(arch, prof.feature_dim, 16, prof.num_classes,
                           heads=2)
            mp = plan_model(spec, prof.num_nodes, prof.num_edges)
            if arch != "gcn":
                assert not any(p.fused for p in mp.layers)

    def test_summary_renders(self):
        prof = DATASETS["cora"]
        spec = ZooSpec("gcn", prof.feature_dim, 16, prof.num_classes)
        mp = plan_model(spec, prof.num_nodes, prof.num_edges)
        s = mp.summary()
        assert "gcn" in s and "fused" in s


class TestGNNServing:
    def _engine(self, archs=("gcn", "gat")):
        eng = GNNServeEngine(max_shard_n=128)
        ds = make_dataset("cora", seed=0, scale=0.08)
        eng.register_graph("cora", ds)
        for a in archs:
            eng.register_model(a, ZooSpec(a, ds.profile.feature_dim, 8,
                                          ds.profile.num_classes,
                                          num_layers=2, heads=2))
        return eng, ds

    def test_predictions_match_direct_forward(self):
        eng, ds = self._engine(archs=("gcn",))
        ids = np.array([0, 3, 17, 40])
        [pred] = eng.serve([NodeRequest("cora", ids, model="gcn")])
        spec = eng._models["gcn"].spec
        params = eng._models["gcn"].params
        mp = eng.model_plan("gcn", "cora")
        gt = build_graph_tensors(ds.edges, ds.profile.num_nodes, mp.shard_n,
                                 "gcn")
        logits = forward(spec, params, gt,
                         gt.group(jnp.asarray(ds.features)),
                         plans=mp.layers)
        np.testing.assert_array_equal(
            pred.classes, np.argmax(np.asarray(logits)[ids], axis=-1))
        assert pred.probs.shape == (4,)
        assert np.all((pred.probs > 0) & (pred.probs <= 1))

    def test_cache_hits_and_batching(self):
        eng, ds = self._engine()
        n = ds.profile.num_nodes
        reqs = [NodeRequest("cora", np.array([i % n, (i * 7) % n]),
                            model=("gcn" if i % 2 else "gat"))
                for i in range(10)]
        preds = eng.serve(reqs)
        assert len(preds) == 10
        # answers come back in request order with the right routing
        for r, p in zip(reqs, preds):
            assert p.model == r.model and p.graph == r.graph
            np.testing.assert_array_equal(p.node_ids, r.node_ids)
        s = eng.stats
        # 2 (model, graph) pairs -> 2 logits misses, everything else hits
        assert s["logits_cache_misses"] == 2
        assert s["logits_cache_hits"] == 8
        assert s["batches"] == 2
        # a second pass over the same traffic is all cache hits
        preds2 = eng.serve(reqs)
        assert eng.stats["logits_cache_misses"] == 2
        np.testing.assert_array_equal(preds2[0].classes, preds[0].classes)

    def test_per_request_latency_attribution(self):
        """Regression: a two-request (model, graph) group must NOT report
        the whole group's wall time (compile included) for every request —
        the cold full-graph forward is charged to the request that
        triggered it, the second pays only its gather, and compile time
        stays out of request latency entirely."""
        eng, ds = self._engine(archs=("gcn",))
        [p1, p2] = eng.serve([
            NodeRequest("cora", np.array([0, 1]), model="gcn"),
            NodeRequest("cora", np.array([2, 3]), model="gcn")])
        assert p1.engine_ms > 0 and p2.engine_ms > 0
        # the full-graph forward dominates a pure gather by orders of
        # magnitude; identical values would mean group-wall misattribution
        assert p2.engine_ms < p1.engine_ms
        # no queueing in the sync path; latency_ms = queue_ms + engine_ms
        assert p1.queue_ms == 0.0 and p2.queue_ms == 0.0
        assert p1.latency_ms == pytest.approx(p1.engine_ms)
        # compile time accrues to engine stats, not to any request
        assert eng.stats["compile_ms_total"] > 0

    def test_graph_cache_shared_by_signature(self):
        """gat and sage_max both need ('sum', self-loops) GraphTensors:
        one build serves both (GNNIE-style graph-specific caching)."""
        eng, ds = self._engine(archs=("gat", "sage_max"))
        eng.serve([NodeRequest("cora", np.array([1]), model="gat"),
                   NodeRequest("cora", np.array([2]), model="sage_max")])
        assert eng.stats["graph_cache_misses"] == 1
        assert eng.stats["graph_cache_hits"] == 1

    def test_invalidate_on_model_update(self):
        eng, ds = self._engine(archs=("gcn",))
        [p1] = eng.serve([NodeRequest("cora", np.array([5]), model="gcn")])
        miss0 = eng.stats["logits_cache_misses"]
        # re-registering (weight swap) must drop the stale logits
        eng.register_model("gcn", eng._models["gcn"].spec, seed=9)
        [p2] = eng.serve([NodeRequest("cora", np.array([5]), model="gcn")])
        assert eng.stats["logits_cache_misses"] == miss0 + 1

    def test_unknown_names_and_bad_ids_raise(self):
        eng, ds = self._engine(archs=("gcn",))
        with pytest.raises(KeyError):
            eng.serve([NodeRequest("nope", np.array([0]), model="gcn")])
        with pytest.raises(KeyError):
            eng.serve([NodeRequest("cora", np.array([0]), model="nope")])
        with pytest.raises(IndexError):
            eng.serve([NodeRequest("cora", np.array([10 ** 9]),
                                   model="gcn")])
