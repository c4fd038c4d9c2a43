"""repro.runtime: the compile() -> Executable API, plan serialization +
memoization, backend parity per zoo arch, and the module forward the
Executable runs."""
import json
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import runtime
from repro.gnn import executor
from repro.gnn.models import ARCHS, ZooSpec
from repro.graphs.datasets import TABLE2_DATASETS, make_dataset

# small enough that pallas interpret mode stays fast, scaled per dataset so
# every Table-II profile is exercised with a multi-shard grid
SCALES = {"cora": 0.02, "citeseer": 0.015, "pubmed": 0.003}


def _spec(arch, prof, hidden=8):
    return ZooSpec(arch, prof.feature_dim, hidden, prof.num_classes,
                   num_layers=2, heads=2)


class TestCompile:
    def test_executable_owns_plan_graph_params(self):
        ds = make_dataset("cora", seed=0, scale=0.05)
        exe = runtime.compile(_spec("gcn", ds.profile), ds,
                              backend="reference", max_shard_n=64)
        assert exe.plan.arch == "gcn"
        assert exe.backend_name == "reference"
        assert exe.gt.S == exe.plan.layers[0].S or exe.gt.n <= 64
        logits = exe.forward()
        assert logits.shape == (ds.profile.num_nodes, ds.profile.num_classes)
        assert "Executable[gcn]" in exe.summary()

    def test_forward_accepts_params_and_features(self):
        ds = make_dataset("cora", seed=0, scale=0.05)
        exe = runtime.compile(_spec("gcn", ds.profile), ds,
                              backend="reference", max_shard_n=64)
        base = np.asarray(exe.forward())
        # explicit features: same numbers
        np.testing.assert_allclose(
            np.asarray(exe.forward(features=ds.features)), base,
            atol=1e-6, rtol=1e-6)
        # fresh params: different numbers, same differentiable entry point
        p2 = runtime.compile(_spec("gcn", ds.profile), ds,
                             backend="reference", max_shard_n=64,
                             seed=3).params
        assert not np.allclose(np.asarray(exe.forward(p2)), base)
        grads = jax.grad(lambda p: exe.forward(p).sum())(exe.params)
        assert jax.tree_util.tree_structure(
            grads) == jax.tree_util.tree_structure(exe.params)

    def test_node_batch_entry_points(self):
        ds = make_dataset("citeseer", seed=0, scale=0.05)
        exe = runtime.compile(_spec("gat", ds.profile), ds,
                              backend="reference", max_shard_n=64)
        ids = np.array([0, 5, 11])
        full = np.asarray(exe.forward())
        np.testing.assert_allclose(np.asarray(exe.forward_nodes(ids)),
                                   full[ids], atol=1e-6)
        assert not exe.has_cached_probs
        classes, probs = exe.predict(ids)
        assert exe.has_cached_probs
        np.testing.assert_array_equal(classes,
                                      np.argmax(full[ids], axis=-1))
        assert np.all((probs > 0) & (probs <= 1))
        exe.invalidate()
        assert not exe.has_cached_probs

    def test_graph_tuple_input_and_fingerprint_sharing(self):
        ds = make_dataset("cora", seed=0, scale=0.05)
        store = runtime.GraphStore()
        spec = _spec("gcn", ds.profile)
        graph = (ds.edges, ds.profile.num_nodes, ds.features)
        e1 = runtime.compile(spec, graph, backend="reference",
                             max_shard_n=64, store=store)
        e2 = runtime.compile(spec, graph, backend="reference",
                             max_shard_n=64, store=store)
        # identical content -> same fingerprint -> one shard build
        assert store.stats["misses"] == 1 and store.stats["hits"] == 1
        assert e1.gt is e2.gt

    def test_fingerprint_distinguishes_features(self):
        """Regression: same topology + different features must not share a
        GraphStore entry (the entry caches the grouped feature tensor)."""
        ds = make_dataset("cora", seed=0, scale=0.05)
        store = runtime.GraphStore()
        spec = _spec("gcn", ds.profile)
        feats2 = ds.features + 1.0
        e1 = runtime.compile(spec, (ds.edges, ds.profile.num_nodes,
                                    ds.features), backend="reference",
                             max_shard_n=64, store=store, seed=0)
        e2 = runtime.compile(spec, (ds.edges, ds.profile.num_nodes, feats2),
                             backend="reference", max_shard_n=64,
                             store=store, seed=0)
        assert e1.graph_key != e2.graph_key
        assert not np.allclose(np.asarray(e1.forward()),
                               np.asarray(e2.forward()))

    def test_compile_pins_the_env_backend(self, monkeypatch):
        """With no backend argument the compile resolves
        REPRO_KERNEL_BACKEND once; changing it afterwards (even to a name
        no backend has) re-routes nothing."""
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "reference")
        ds = make_dataset("cora", seed=0, scale=0.05)
        exe = runtime.compile(_spec("sage_max", ds.profile), ds,
                              max_shard_n=64)
        assert exe.backend is runtime.get_backend("reference")
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "fpga")
        out = np.asarray(exe.forward())
        assert exe.backend is runtime.get_backend("reference")
        pinned = runtime.compile(_spec("sage_max", ds.profile), ds,
                                 backend="reference", max_shard_n=64)
        np.testing.assert_array_equal(out, np.asarray(pinned.forward()))

    def test_explicit_backend_beats_env_at_compile(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNEL_BACKEND", "jax")
        ds = make_dataset("cora", seed=0, scale=0.05)
        exe = runtime.compile(_spec("sage_max", ds.profile), ds,
                              backend="reference", max_shard_n=64)
        assert exe.backend is runtime.get_backend("reference")

    def test_params_roundtrip(self, tmp_path):
        ds = make_dataset("cora", seed=0, scale=0.05)
        exe = runtime.compile(_spec("gin", ds.profile), ds,
                              backend="reference", max_shard_n=64)
        before = np.asarray(exe.forward())
        exe.save_params(tmp_path / "p.npz")
        exe.save_plan(tmp_path / "plan.json")
        # perturb, then restore from disk
        exe.set_params(jax.tree_util.tree_map(lambda x: x * 0, exe.params))
        assert not np.allclose(np.asarray(exe.forward()), before)
        exe.load_params(tmp_path / "p.npz")
        np.testing.assert_allclose(np.asarray(exe.forward()), before,
                                   atol=1e-6)
        plan = executor.ModelPlan.from_json(
            json.loads((tmp_path / "plan.json").read_text()))
        assert plan == exe.plan


class TestGraphStoreEviction:
    def test_executable_serves_after_lru_eviction_and_rebuilds_on_miss(self):
        """Eviction-under-use: an Executable owns its GraphTensors, so LRU
        eviction of its store entry must not break serving; the next
        compile for that graph rebuilds on miss, visibly (built_ms_total
        counts rebuild churn)."""
        store = runtime.GraphStore(max_entries=1)
        ds_a = make_dataset("cora", seed=0, scale=0.05)
        ds_b = make_dataset("citeseer", seed=0, scale=0.05)
        kw = dict(backend="reference", max_shard_n=64, store=store, seed=0)
        spec_a = _spec("gcn", ds_a.profile)
        exe_a = runtime.compile(spec_a, ds_a, graph_key="a", **kw)
        ref = np.asarray(exe_a.forward())
        built_after_a = store.stats["built_ms_total"]
        assert built_after_a > 0

        # compiling for graph b evicts a's (sole-capacity) store entry
        runtime.compile(_spec("gcn", ds_b.profile), ds_b, graph_key="b",
                        **kw)
        assert store.stats["evictions"] == 1
        assert store.stats["built_ms_total"] > built_after_a

        # the evicted Executable keeps serving correctly, including a
        # full recompute of its cached softmax after invalidation
        classes, _ = exe_a.predict(np.array([0, 1, 2]))
        np.testing.assert_array_equal(classes,
                                      np.argmax(ref[:3], axis=-1))
        exe_a.invalidate()
        np.testing.assert_allclose(np.asarray(exe_a.forward()), ref,
                                   atol=1e-6, rtol=1e-6)

        # rebuild-on-miss: a fresh compile for graph a cannot hit
        misses0 = store.stats["misses"]
        built0 = store.stats["built_ms_total"]
        exe_a2 = runtime.compile(spec_a, ds_a, graph_key="a", **kw)
        assert store.stats["misses"] == misses0 + 1
        assert store.stats["built_ms_total"] > built0
        np.testing.assert_allclose(np.asarray(exe_a2.forward()), ref,
                                   atol=1e-5, rtol=1e-5)


class TestBackendParity:
    """Acceptance: compile(..., backend="reference") produces logits
    allclose to backend="pallas" for every zoo arch on the Table-II
    datasets (scaled down: pallas runs in interpret mode on CPU)."""

    @pytest.mark.parametrize("arch", ARCHS)
    @pytest.mark.parametrize("dataset", sorted(TABLE2_DATASETS))
    def test_reference_matches_pallas(self, arch, dataset):
        ds = make_dataset(dataset, seed=1, scale=SCALES[dataset])
        spec = _spec(arch, ds.profile)
        store = runtime.GraphStore()
        kw = dict(max_shard_n=16, store=store, graph_key=dataset, seed=0)
        ref_exe = runtime.compile(spec, ds, backend="reference", **kw)
        pal_exe = runtime.compile(spec, ds, backend="pallas", **kw)
        assert ref_exe.plan is pal_exe.plan     # content-hash memo shares
        np.testing.assert_allclose(
            np.asarray(pal_exe.forward()), np.asarray(ref_exe.forward()),
            atol=1e-4, rtol=1e-4)


class TestPlanCacheAndSerialization:
    def test_plan_json_roundtrip(self):
        prof = TABLE2_DATASETS["cora"]
        spec = ZooSpec("gat", prof.feature_dim, 16, prof.num_classes,
                       num_layers=3, heads=2)
        plan = executor.plan_model(spec, prof.num_nodes, prof.num_edges)
        blob = json.dumps(plan.to_json())
        back = executor.ModelPlan.from_json(json.loads(blob))
        assert back == plan
        assert back.layers[0].order == plan.layers[0].order
        assert back.shard_n == plan.shard_n

    def test_plan_model_content_hash_memo(self):
        executor.clear_plan_cache()
        prof = TABLE2_DATASETS["citeseer"]
        spec = ZooSpec("gcn", prof.feature_dim, 16, prof.num_classes)
        p1 = executor.plan_model(spec, prof.num_nodes, prof.num_edges)
        p2 = executor.plan_model(spec, prof.num_nodes, prof.num_edges)
        assert p1 is p2
        stats = executor.plan_cache_stats()
        assert stats["misses"] == 1 and stats["hits"] == 1
        # any input that shapes the plan is part of the key
        executor.plan_model(spec, prof.num_nodes, prof.num_edges, max_n=64)
        assert executor.plan_cache_stats()["misses"] == 2

    def test_plan_disk_cache_skips_replanning(self, tmp_path):
        executor.clear_plan_cache()
        prof = TABLE2_DATASETS["cora"]
        spec = ZooSpec("sage_mean", prof.feature_dim, 16, prof.num_classes)
        p1 = executor.plan_model(spec, prof.num_nodes, prof.num_edges,
                                 cache_dir=tmp_path)
        assert len(list(tmp_path.glob("*.json"))) == 1
        # a "restarted" process: fresh in-memory cache, same disk dir
        executor.clear_plan_cache()
        p2 = executor.plan_model(spec, prof.num_nodes, prof.num_edges,
                                 cache_dir=tmp_path)
        assert p2 == p1
        assert executor.plan_cache_stats()["disk_hits"] == 1
        assert executor.plan_cache_stats()["misses"] == 0


class TestModuleForward:
    @pytest.mark.parametrize("arch", ARCHS)
    def test_module_forward_matches_executable(self, arch):
        """runtime.forward.forward on a graph built by
        build_graph_tensors, at the compiled plans and backend, is what
        Executable.forward() runs."""
        from repro.runtime.forward import build_graph_tensors, forward
        ds = make_dataset("cora", seed=0, scale=0.05)
        exe = runtime.compile(_spec(arch, ds.profile), ds,
                              backend="reference", max_shard_n=64)
        gt = build_graph_tensors(ds.edges, ds.profile.num_nodes,
                                 exe.plan.shard_n, arch)
        out = forward(exe.spec, exe.params, gt,
                      gt.group(jnp.asarray(ds.features)),
                      plans=exe.plan.layers, backend=exe.backend)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exe.forward()),
                                   atol=1e-5, rtol=1e-5)

    def test_new_consumers_emit_no_deprecation_warnings(self):
        ds = make_dataset("cora", seed=0, scale=0.05)
        from repro.serving.gnn_engine import GNNServeEngine, NodeRequest
        eng = GNNServeEngine(max_shard_n=64, backend="reference")
        eng.register_graph("cora", ds)
        eng.register_model("gcn", _spec("gcn", ds.profile))
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            eng.serve([NodeRequest("cora", np.array([0, 1]), model="gcn")])


class TestServingOnRuntime:
    def test_engine_caches_executables(self):
        ds = make_dataset("cora", seed=0, scale=0.05)
        from repro.serving.gnn_engine import GNNServeEngine, NodeRequest
        eng = GNNServeEngine(max_shard_n=64, backend="reference")
        eng.register_graph("cora", ds)
        eng.register_model("gcn", _spec("gcn", ds.profile))
        exe = eng.executable("gcn", "cora")
        assert isinstance(exe, runtime.Executable)
        eng.serve([NodeRequest("cora", np.array([0]), model="gcn")])
        assert eng.executable("gcn", "cora") is exe
        assert eng.stats["compiles"] == 1
        # weight swap drops the compiled unit
        eng.register_model("gcn", _spec("gcn", ds.profile), seed=5)
        assert eng.executable("gcn", "cora") is not exe


class TestNodeIdValidation:
    """Negative ids used to wrap around (numpy indexing) and return the
    WRONG node's prediction; ids >= N clamped/wrapped. Both must raise."""

    def _exe(self):
        ds = make_dataset("cora", seed=0, scale=0.05)
        return ds, runtime.compile(_spec("gcn", ds.profile), ds,
                                   backend="reference", max_shard_n=64)

    def test_predict_rejects_out_of_range_ids(self):
        ds, exe = self._exe()
        n = ds.profile.num_nodes
        with pytest.raises(ValueError, match="node ids"):
            exe.predict([-1])
        with pytest.raises(ValueError, match="node ids"):
            exe.predict([0, n])
        # valid boundary ids still work
        classes, probs = exe.predict([0, n - 1])
        assert classes.shape == (2,)

    def test_forward_nodes_rejects_out_of_range_ids(self):
        ds, exe = self._exe()
        with pytest.raises(ValueError, match="node ids"):
            exe.forward_nodes([-3])
        with pytest.raises(ValueError, match="node ids"):
            exe.forward_nodes([ds.profile.num_nodes + 7])

    def test_stale_ids_surface_as_typed_failed_outcome(self):
        """A request validated by route() against the profile at admission
        can still hit a smaller graph at step time (re-registration race);
        the Executable's ValueError must come back as a typed Failed for
        THAT request only — a valid request sharing the micro-batch still
        completes."""
        from repro.serving import Completed, Failed, SchedulerConfig, Server
        from repro.serving.gnn_engine import GNNServeEngine, NodeRequest

        big = make_dataset("cora", seed=0, scale=0.05)
        small = make_dataset("cora", seed=0, scale=0.02)
        eng = GNNServeEngine(max_shard_n=64, backend="reference")
        eng.register_graph("cora", big)
        eng.register_model("gcn", _spec("gcn", big.profile))
        server = Server(eng, SchedulerConfig(max_batch_size=2))
        bad = server.submit(NodeRequest(
            "cora", np.array([big.profile.num_nodes - 1]), "gcn"))
        ok = server.submit(NodeRequest("cora", np.array([0]), "gcn"))
        # shrink the graph after admission, before dispatch: both requests
        # are already queued on the same (model, graph) stream
        eng.register_graph("cora", small)
        server.drain()
        out = bad.result()
        assert isinstance(out, Failed)
        assert "node ids" in out.error
        # the co-batched valid request is NOT poisoned by its neighbor
        assert isinstance(ok.result(), Completed)
        m = server.metrics()
        assert m["failed"] == 1 and m["completed"] == 1


class TestParamSerializationRobustness:
    def test_unflatten_handles_non_contiguous_digit_keys(self):
        from repro.runtime.executable import (_flatten_params,
                                              _unflatten_params)
        tree = {"layers": [{"w": np.ones((2, 2))},
                           {"w": np.full((2, 2), 2.0)},
                           {"w": np.full((2, 2), 3.0)}]}
        flat = _flatten_params(tree)
        # prune the middle layer, as a pruned/partial checkpoint would
        pruned = {k: v for k, v in flat.items() if "/1/" not in k}
        rebuilt = _unflatten_params(pruned)
        assert len(rebuilt["layers"]) == 2
        np.testing.assert_array_equal(np.asarray(rebuilt["layers"][0]["w"]),
                                      flat["layers/0/w"])
        np.testing.assert_array_equal(np.asarray(rebuilt["layers"][1]["w"]),
                                      flat["layers/2/w"])

    def test_load_params_roundtrip_with_pruned_checkpoint(self, tmp_path):
        ds = make_dataset("cora", seed=0, scale=0.05)
        spec = _spec("gcn", ds.profile)
        exe = runtime.compile(spec, ds, backend="reference", max_shard_n=64)
        path = tmp_path / "params.npz"
        exe.save_params(path)
        # rewrite the archive with a gap in the layer indices: layer 1
        # saved under index 3 (a partial export / manual surgery case)
        with np.load(path) as z:
            flat = {k.replace("layers/1/", "layers/3/"): z[k] for k in z}
        np.savez(path, **flat)
        loaded = exe.load_params(path)     # must not KeyError
        assert len(loaded["layers"]) == len(spec.layer_dims)
        logits = exe.forward()             # still runs end to end
        assert logits.shape == (ds.profile.num_nodes, ds.profile.num_classes)
