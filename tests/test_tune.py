"""repro.tune: autotuner determinism + memoization, winner-store key
scoping (backend/platform/version), corruption/staleness fallbacks, and
analytic-vs-autotuned output parity through runtime.compile."""
import json

import numpy as np
import pytest

from repro import runtime, tune
from repro.gnn import executor
from repro.gnn.models import ZooSpec
from repro.graphs.datasets import TABLE2_DATASETS, make_dataset
from repro.kernels.registry import KernelBackend, get_backend
from repro.tune.measure import Measurement
from repro.tune.store import TUNER_VERSION, TuneRecord

# scaled so every Table-II profile still yields a multi-shard grid but
# each tuning rep stays milliseconds on the reference backend
SCALES = {"cora": 0.02, "citeseer": 0.015, "pubmed": 0.003}


def _setup(dataset="cora", arch="gcn", scale=0.05, hidden=8):
    ds = make_dataset(dataset, seed=0, scale=scale)
    spec = ZooSpec(arch, ds.profile.feature_dim, hidden,
                   ds.profile.num_classes, num_layers=2)
    return ds, spec


@pytest.fixture(autouse=True)
def _fresh_tune_cache():
    tune.clear_tune_cache()
    yield
    tune.clear_tune_cache()


class _BrokenBackend:
    """Every op raises: models a backend that OOMs/faults on any config."""
    name = "broken"


def _boom(*_a, **_kw):
    raise RuntimeError("kernel exploded")


for _op, _fn in vars(KernelBackend).items():   # the protocol's ops
    if callable(_fn) and not _op.startswith("_"):
        setattr(_BrokenBackend, _op, staticmethod(_boom))


class TestSearch:
    def test_analytic_is_candidate_zero(self):
        ds, spec = _setup()
        analytic = executor.plan_model(spec, ds.profile.num_nodes,
                                       ds.edges.shape[0], max_n=64)
        cands = tune.candidate_plans(spec, ds.profile.num_nodes,
                                     ds.edges.shape[0], analytic=analytic,
                                     max_n=64, budget=8)
        assert cands, "search produced no candidates"
        assert tune.plan_digest(cands[0]) == tune.plan_digest(analytic)
        digests = [tune.plan_digest(c) for c in cands]
        assert len(set(digests)) == len(digests)   # deduped
        assert len(cands) <= 8

    def test_enumeration_is_deterministic(self):
        ds, spec = _setup("citeseer", scale=0.02)
        analytic = executor.plan_model(spec, ds.profile.num_nodes,
                                       ds.edges.shape[0], max_n=64)
        kw = dict(analytic=analytic, max_n=64, top_k=3, budget=12)
        a = tune.candidate_plans(spec, ds.profile.num_nodes,
                                 ds.edges.shape[0], **kw)
        b = tune.candidate_plans(spec, ds.profile.num_nodes,
                                 ds.edges.shape[0], **kw)
        assert [tune.plan_digest(p) for p in a] == \
               [tune.plan_digest(p) for p in b]

    def test_budget_truncates(self):
        ds, spec = _setup()
        analytic = executor.plan_model(spec, ds.profile.num_nodes,
                                       ds.edges.shape[0], max_n=64)
        cands = tune.candidate_plans(spec, ds.profile.num_nodes,
                                     ds.edges.shape[0], analytic=analytic,
                                     max_n=64, budget=2)
        assert len(cands) <= 2
        assert tune.candidate_plans(
            spec, ds.profile.num_nodes, ds.edges.shape[0],
            analytic=analytic, max_n=64, budget=0) == []


class TestStaticPruning:
    """Plan-lint pruning runs inside candidate_plans: doomed or
    execution-identical configs are rejected statically — before any
    measurement — and the tune report says so."""

    def test_pruning_never_removes_analytic_candidate(self):
        ds, spec = _setup()
        analytic = executor.plan_model(spec, ds.profile.num_nodes,
                                       ds.edges.shape[0], max_n=64)
        pruned = []
        cands = tune.candidate_plans(spec, ds.profile.num_nodes,
                                     ds.edges.shape[0], analytic=analytic,
                                     max_n=64, budget=8,
                                     backend_name="reference",
                                     pruned_out=pruned)
        assert tune.plan_digest(cands[0]) == tune.plan_digest(analytic)
        # both traversal orders are enumerated but the runtime executes
        # them identically, so the cora space always holds duplicates
        assert pruned, "expected >= 1 statically-pruned candidate"
        for rec in pruned:
            assert rec["index"] > 0          # analytic #0 is untouchable
            assert rec["reason"] in ("illegal", "duplicate-execution")
            assert rec["detail"]

        from repro.analyze import plan_lint
        for c in cands:                      # kept => legal
            assert [f for f in plan_lint.check_model_plan(
                c, backend_name="reference")
                if f.severity == "error"] == []
        digests = [plan_lint.executed_digest(c) for c in cands]
        assert len(set(digests)) == len(digests)   # kept => distinct program

    def test_tune_report_records_pruned(self, tmp_path):
        ds, spec = _setup()
        be = get_backend("reference")
        rec = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes,
                                 backend=be, features=ds.features, max_n=64,
                                 budget=6, reps=1, cache_dir=tmp_path)
        rep = rec.report()
        assert rep["candidates_pruned"] == len(rec.pruned)
        assert rep["candidates_pruned"] >= 1
        assert sum(rep["pruned_reasons"].values()) == rep["candidates_pruned"]
        # pruned classes never reach measurement, so nothing fails there
        assert rep["candidates_failed"] == 0
        back = TuneRecord.from_json(json.loads(json.dumps(rec.to_json())))
        assert back.pruned == rec.pruned


class TestAutotuneMemoization:
    """Same (arch, graph signature, budget, seed) -> identical winner with
    zero re-measurement on the second call."""

    def test_deterministic_and_memoized_in_process(self):
        ds, spec = _setup()
        be = get_backend("reference")
        kw = dict(backend=be, features=ds.features, max_n=64,
                  budget=4, seed=0, reps=2, warmup=1)
        rec1 = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes, **kw)
        stats = tune.tune_cache_stats()
        assert rec1.plan_source == "autotune"
        assert stats["measurements"] == rec1.n_measured > 0
        rec2 = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes, **kw)
        stats = tune.tune_cache_stats()
        assert rec2 is rec1                       # in-process memo
        assert stats["hits"] == 1
        assert stats["measurements"] == rec1.n_measured   # nothing re-run
        assert tune.plan_digest(rec2.plan) == tune.plan_digest(rec1.plan)

    def test_disk_memo_survives_restart(self, tmp_path):
        ds, spec = _setup()
        be = get_backend("reference")
        kw = dict(backend=be, features=ds.features, max_n=64,
                  budget=3, seed=1, reps=2, cache_dir=tmp_path)
        rec1 = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes, **kw)
        assert list(tmp_path.glob("tune-*.json"))
        tune.clear_tune_cache()                   # "new process"
        rec2 = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes, **kw)
        stats = tune.tune_cache_stats()
        assert stats["disk_hits"] == 1 and stats["measurements"] == 0
        assert tune.plan_digest(rec2.plan) == tune.plan_digest(rec1.plan)
        assert rec2.winner_ms == rec1.winner_ms

    def test_corrupt_disk_entry_falls_back_to_retuning(self, tmp_path):
        ds, spec = _setup()
        be = get_backend("reference")
        key = tune.tune_key(spec, ds.profile.num_nodes, ds.edges.shape[0],
                            platform=executor.GNNERATOR, max_n=64,
                            block_candidates=executor._BLOCK_CANDIDATES,
                            backend_name=be.name, budget=3, seed=0,
                            reps=2, warmup=1)
        (tmp_path / f"tune-{key}.json").write_text("{not json!!")
        rec = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes,
                                 backend=be, features=ds.features, max_n=64,
                                 budget=3, seed=0, reps=2, warmup=1,
                                 cache_dir=tmp_path)
        stats = tune.tune_cache_stats()
        assert stats["corrupt"] == 1              # degraded, not raised
        assert rec.plan_source == "autotune"
        assert stats["measurements"] == rec.n_measured > 0

    def test_stale_tuner_version_invalidates(self, tmp_path):
        ds, spec = _setup()
        be = get_backend("reference")
        kw = dict(backend=be, features=ds.features, max_n=64,
                  budget=3, seed=2, reps=2, cache_dir=tmp_path)
        tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes, **kw)
        (path,) = tmp_path.glob("tune-*.json")
        blob = json.loads(path.read_text())
        blob["tuner_version"] = TUNER_VERSION + 1
        path.write_text(json.dumps(blob))
        tune.clear_tune_cache()
        rec = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes, **kw)
        stats = tune.tune_cache_stats()
        assert stats["corrupt"] == 1 and stats["disk_hits"] == 0
        assert rec.plan_source == "autotune"      # re-tuned from scratch
        assert stats["measurements"] > 0

    def test_budget_zero_is_analytic_fallback(self):
        ds, spec = _setup()
        rec = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes,
                                 backend=get_backend("reference"),
                                 max_n=64, budget=0)
        assert rec.plan_source == "analytic_fallback"
        assert rec.n_measured == 0 and rec.winner_ms is None
        assert tune.tune_cache_stats()["measurements"] == 0
        analytic = executor.plan_model(spec, ds.profile.num_nodes,
                                       ds.edges.shape[0], max_n=64)
        assert tune.plan_digest(rec.plan) == tune.plan_digest(analytic)

    def test_all_candidates_failing_never_raises(self):
        ds, spec = _setup()
        rec = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes,
                                 backend=_BrokenBackend(),
                                 features=ds.features, max_n=64,
                                 budget=3, reps=1)
        assert rec.plan_source == "analytic_fallback"
        assert rec.n_measured > 0
        assert all(m.status == "error" for m in rec.candidates)
        assert all("RuntimeError" in m.error for m in rec.candidates)
        rep = rec.report()
        assert rep["candidates_failed"] == rec.n_measured


class TestKeyScoping:
    """Satellite regression: a winner measured on one backend/platform/
    version must never be served to another, while the *analytic* plan
    memo stays environment-independent (backends share plan objects)."""

    def _key(self, spec, ds, **over):
        kw = dict(platform=executor.GNNERATOR, max_n=64,
                  block_candidates=executor._BLOCK_CANDIDATES,
                  backend_name="reference", budget=4, seed=0, reps=3,
                  warmup=1)
        kw.update(over)
        return tune.tune_key(spec, ds.profile.num_nodes,
                             ds.edges.shape[0], **kw)

    def test_every_scope_axis_is_in_the_key(self):
        ds, spec = _setup()
        base = self._key(spec, ds)
        assert base == self._key(spec, ds)                    # stable
        assert base != self._key(spec, ds, backend_name="pallas")
        assert base != self._key(spec, ds, budget=5)
        assert base != self._key(spec, ds, seed=1)
        assert base != self._key(spec, ds, reps=2)
        assert base != self._key(spec, ds, warmup=2)

    def test_scope_includes_environment(self):
        import jax
        scope = tune.tune_scope("pallas")
        assert scope["backend"] == "pallas"
        assert scope["jax_platform"] == jax.default_backend()
        assert scope["jax_version"] == jax.__version__
        assert scope["tuner_version"] == TUNER_VERSION

    def test_analytic_plan_key_ignores_scope_only_when_absent(self):
        ds, spec = _setup()
        bare = executor.plan_key(spec, ds.profile.num_nodes,
                                 ds.edges.shape[0],
                                 platform=executor.GNNERATOR, max_n=64,
                                 block_candidates=executor._BLOCK_CANDIDATES)
        scoped = executor.plan_key(spec, ds.profile.num_nodes,
                                   ds.edges.shape[0],
                                   platform=executor.GNNERATOR, max_n=64,
                                   block_candidates=executor._BLOCK_CANDIDATES,
                                   scope={"backend": "pallas"})
        assert bare != scoped
        assert bare == executor.plan_key(
            spec, ds.profile.num_nodes, ds.edges.shape[0],
            platform=executor.GNNERATOR, max_n=64,
            block_candidates=executor._BLOCK_CANDIDATES, scope=None)

    def test_winners_not_shared_across_backends(self):
        ds, spec = _setup(scale=0.02)
        kw = dict(features=ds.features, max_n=16, budget=2, reps=1)
        tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes,
                           backend=get_backend("reference"), **kw)
        n_ref = tune.tune_cache_stats()["measurements"]
        assert n_ref > 0
        tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes,
                           backend=get_backend("jax"), **kw)
        stats = tune.tune_cache_stats()
        assert stats["misses"] == 2               # distinct keys: re-tuned
        assert stats["measurements"] > n_ref

    def test_analytic_plans_still_shared_across_backends(self):
        ds, spec = _setup(scale=0.02)
        store = runtime.GraphStore()
        kw = dict(max_shard_n=16, store=store, graph_key="cora-tiny", seed=0)
        ref = runtime.compile(spec, ds, backend="reference", **kw)
        jx = runtime.compile(spec, ds, backend="jax", **kw)
        assert ref.plan is jx.plan                # content-hash memo shares


class TestRuntimeIntegration:
    def test_compile_autotune_memoizes_and_reports(self):
        ds, spec = _setup()
        store = runtime.GraphStore()
        kw = dict(backend="reference", plan="autotune", tune_budget=3,
                  tune_reps=2, max_shard_n=64, store=store,
                  graph_key="cora-s05")
        exe = runtime.compile(spec, ds, **kw)
        assert exe.plan_source == "autotune"
        assert exe.tune_report["candidates_measured"] > 0
        head, *rest = exe.summary().splitlines()
        assert "plan=autotune" in head
        assert any("autotune: winner" in ln for ln in rest)
        n = runtime.tune_cache_stats()["measurements"]
        exe2 = runtime.compile(spec, ds, **kw)
        assert runtime.tune_cache_stats()["measurements"] == n   # cache hit
        assert exe2.plan == exe.plan

    def test_compile_budget_zero_reports_fallback(self):
        ds, spec = _setup()
        exe = runtime.compile(spec, ds, backend="reference", plan="autotune",
                              tune_budget=0, max_shard_n=64)
        assert exe.plan_source == "analytic_fallback"
        assert "autotune: analytic fallback" in exe.summary()
        logits = exe.forward()
        assert logits.shape == (ds.profile.num_nodes,
                                ds.profile.num_classes)

    def test_compile_rejects_unknown_plan_source(self):
        ds, spec = _setup()
        with pytest.raises(ValueError, match="plan must be"):
            runtime.compile(spec, ds, backend="reference", plan="magic")

    def test_compile_rejects_autotune_on_mesh(self):
        ds, spec = _setup()
        with pytest.raises(ValueError, match="mesh"):
            runtime.compile(spec, ds, backend="reference", plan="autotune",
                            mesh=object())

    def test_measurement_json_roundtrip(self):
        m = Measurement(digest="abc", config=[{"layer": 0, "B": 8}],
                        status="ok", median_ms=1.25, reps_ms=(1.3, 1.25, 1.2),
                        warmup_ms=5.0)
        back = Measurement.from_json(json.loads(json.dumps(m.to_json())))
        assert back == m

    def test_tune_record_json_roundtrip(self, tmp_path):
        ds, spec = _setup()
        be = get_backend("reference")
        rec = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes,
                                 backend=be, features=ds.features, max_n=64,
                                 budget=2, reps=1, cache_dir=tmp_path)
        back = TuneRecord.from_json(json.loads(json.dumps(rec.to_json())))
        assert back.plan == rec.plan
        assert back.plan_source == rec.plan_source
        assert back.candidates == rec.candidates


class TestParity:
    """CI acceptance: autotuned-plan outputs match analytic-plan outputs
    (the tuner may only change *how* a layer runs, never its math)."""

    @pytest.mark.parametrize("arch", ("gcn", "sage_mean", "gin"))
    @pytest.mark.parametrize("dataset", sorted(TABLE2_DATASETS))
    def test_autotuned_matches_analytic_parity(self, arch, dataset):
        ds = make_dataset(dataset, seed=1, scale=SCALES[dataset])
        spec = ZooSpec(arch, ds.profile.feature_dim, 8,
                       ds.profile.num_classes, num_layers=2)
        store = runtime.GraphStore()
        kw = dict(backend="reference", max_shard_n=16, store=store,
                  graph_key=dataset, seed=0)
        ana = runtime.compile(spec, ds, **kw)
        tuned = runtime.compile(spec, ds, plan="autotune", tune_budget=3,
                                tune_reps=1, **kw)
        assert tuned.plan_source in ("autotune", "analytic_fallback")
        np.testing.assert_allclose(
            np.asarray(tuned.forward()), np.asarray(ana.forward()),
            atol=1e-4, rtol=1e-4)
