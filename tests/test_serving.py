"""Serving engine: batched greedy decode must equal step-by-step argmax of
the full forward pass — directly and through the continuous-batching
Server (prompt-length-bucketed streams) — plus the GNN engine's hot
weight-reload invariants."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import get_smoke
from repro.models import lm
from repro.serving import Completed, Failed, Rejected, SchedulerConfig, Server
from repro.serving.engine import Request, ServeEngine


def test_greedy_matches_forward_argmax():
    cfg = get_smoke("qwen3-8b")
    params = lm.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, max_len=48)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(2)]
    outs = eng.generate([Request(p, max_new_tokens=6) for p in prompts])

    # reference: grow the sequence with full forward argmax each step
    for i, p in enumerate(prompts):
        seq = list(p)
        for _ in range(6):
            logits = lm.forward(params, cfg,
                                {"tokens": jnp.asarray([seq], jnp.int32)})
            seq.append(int(jnp.argmax(logits[0, -1])))
        np.testing.assert_array_equal(outs[i], np.asarray(seq[len(p):]))


def test_multicodebook_generation_shapes():
    cfg = get_smoke("musicgen-large")
    params = lm.init_params(cfg, jax.random.key(1))
    eng = ServeEngine(cfg, params, max_len=32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (8, cfg.n_codebooks)).astype(np.int32)
               for _ in range(2)]
    outs = eng.generate([Request(p, max_new_tokens=4) for p in prompts])
    assert outs[0].shape == (4, cfg.n_codebooks)
    assert (outs[0] >= 0).all() and (outs[0] < cfg.vocab_size).all()


def test_server_buckets_by_prompt_length_and_matches_direct_generate():
    cfg = get_smoke("qwen3-8b")
    params = lm.init_params(cfg, jax.random.key(0))
    eng = ServeEngine(cfg, params, max_len=48)
    rng = np.random.default_rng(4)
    short = [Request(rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                     max_new_tokens=4) for _ in range(3)]
    long = [Request(rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                    max_new_tokens=4) for _ in range(2)]

    srv = Server(eng, SchedulerConfig(max_batch_size=2))
    tickets = [srv.submit(r) for r in short + long]
    assert srv.drain() == 5
    # prompt-length buckets: 8-token prompts form batches [2,1], 12-token [2]
    m = srv.metrics()
    assert m["batches"] == 3 and m["completed"] == 5

    for r, t in zip(short + long, tickets):
        out = t.result()
        assert isinstance(out, Completed)
        # greedy decode is deterministic, so the scheduled batching must
        # reproduce a direct single-request generate exactly
        np.testing.assert_array_equal(out.value,
                                      eng.generate([r], seed=0)[0])

    # over-long prompts are rejected at admission, typed, not raised
    too_long = srv.submit(Request(np.zeros(60, np.int32), max_new_tokens=4))
    out = too_long.poll()
    assert isinstance(out, Rejected) and "max_len" in out.reason


class TestHotReload:
    """Server-level hot weight reload: no recompiles, cache invalidated
    exactly once, in-flight requests survive, post-reload predictions
    match a fresh compile with the new weights."""

    def _engine_and_server(self, ds, spec):
        from repro.serving.gnn_engine import GNNServeEngine
        engine = GNNServeEngine(backend="reference")
        engine.register_graph("cora", ds)
        engine.register_model("gcn", spec, seed=0)
        return engine, Server(engine, SchedulerConfig(max_batch_size=4))

    def _setup(self):
        from repro.gnn.models import ZooSpec
        from repro.graphs.datasets import make_dataset
        ds = make_dataset("cora", seed=0, scale=0.2)
        spec = ZooSpec("gcn", ds.profile.feature_dim, 8,
                       ds.profile.num_classes)
        return ds, spec

    def test_reload_matches_fresh_compile_invalidates_once(self):
        from repro import runtime
        from repro.gnn.models import init_zoo
        from repro.serving.gnn_engine import NodeRequest

        ds, spec = self._setup()
        engine, server = self._engine_and_server(ds, spec)
        ids = np.arange(6)
        t = server.submit(NodeRequest("cora", ids, "gcn"))
        server.drain()
        assert isinstance(t.result(), Completed)
        assert engine.stats["compiles"] == 1

        new_params = init_zoo(jax.random.key(42), spec)
        touched = server.reload(
            lambda eng: eng.reload_params("gcn", new_params))
        assert touched == 1
        assert engine.stats["reloads"] == 1
        assert engine.stats["logits_invalidations"] == 1
        assert server.metrics()["reloads"] == 1

        t2 = server.submit(NodeRequest("cora", ids, "gcn"))
        server.drain()
        out = t2.result()
        assert isinstance(out, Completed)
        # NO recompile happened — the jitted Executable was reused
        assert engine.stats["compiles"] == 1

        fresh = runtime.compile(spec, ds, backend="reference",
                                params=new_params)
        c_ref, p_ref = fresh.predict(ids)
        np.testing.assert_array_equal(out.value.classes, c_ref)
        np.testing.assert_allclose(out.value.probs, p_ref, atol=1e-5)

        # a model registered after the reload-compiles adopt new weights
        exe = engine.executable("gcn", "cora")
        assert all(
            np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(jax.tree.leaves(exe.params),
                            jax.tree.leaves(new_params)))

    def test_reload_does_not_fail_inflight_cobatched_requests(self):
        from repro.gnn.models import init_zoo
        from repro.serving.gnn_engine import NodeRequest

        ds, spec = self._setup()
        engine, server = self._engine_and_server(ds, spec)
        rng = np.random.default_rng(0)
        # queued (in-flight) BEFORE the reload; co-batched on one stream
        tickets = [server.submit(NodeRequest(
            "cora", rng.integers(0, ds.profile.num_nodes, 4), "gcn"))
            for _ in range(6)]
        assert server.queue_depth() == 6
        server.reload(lambda eng: eng.reload_params(
            "gcn", init_zoo(jax.random.key(7), spec)))
        server.drain()
        outs = [t.result() for t in tickets]
        assert all(isinstance(o, Completed) for o in outs), \
            [o for o in outs if isinstance(o, Failed)]
        assert server.metrics()["failed"] == 0

    def test_reload_validation_is_atomic(self):
        from repro.gnn.models import ZooSpec, init_zoo
        from repro.serving.gnn_engine import NodeRequest

        ds, spec = self._setup()
        engine, server = self._engine_and_server(ds, spec)
        t = server.submit(NodeRequest("cora", np.arange(3), "gcn"))
        server.drain()
        assert isinstance(t.result(), Completed)

        wrong = ZooSpec("gcn", ds.profile.feature_dim, 12,
                        ds.profile.num_classes)
        with pytest.raises(ValueError, match="reload"):
            server.reload(lambda eng: eng.reload_params(
                "gcn", init_zoo(jax.random.key(0), wrong)))
        # nothing was touched: cache still warm, params unchanged
        exe = engine.executable("gcn", "cora")
        assert exe.has_cached_probs
        assert engine.stats["reloads"] == 0
        assert engine.stats["logits_invalidations"] == 0
        with pytest.raises(KeyError):
            server.reload(lambda eng: eng.reload_params("nope", {}))


def test_mesh_unsupported_arch_rejected_typed_not_crashed():
    """dist/gnn.py only shards the linear-aggregation family; on a mesh
    engine a sage_max/gat request must come back as a typed Rejected at
    admission — not crash the engine step (which would Fail co-batched
    requests)."""
    from repro.gnn.models import ZooSpec
    from repro.graphs.datasets import make_dataset
    from repro.launch.mesh import make_mesh_for
    from repro.serving.gnn_engine import GNNServeEngine, NodeRequest

    ds = make_dataset("cora", seed=0, scale=0.15)
    mesh = make_mesh_for(jax.device_count(), model_parallel=1)
    engine = GNNServeEngine(backend="reference", max_shard_n=128, mesh=mesh)
    engine.register_graph("cora", ds)
    engine.register_model(
        "pool", ZooSpec("sage_max", ds.profile.feature_dim, 8,
                        ds.profile.num_classes))
    engine.register_model(
        "gcn", ZooSpec("gcn", ds.profile.feature_dim, 8,
                       ds.profile.num_classes))
    server = Server(engine, SchedulerConfig(max_batch_size=4))

    bad = server.submit(NodeRequest("cora", np.arange(4), "pool"))
    out = bad.poll()                       # rejected at admission, typed
    assert isinstance(out, Rejected) and out.kind == "invalid"
    assert "sharded execution supports" in out.reason

    good = server.submit(NodeRequest("cora", np.arange(4), "gcn"))
    server.drain()
    assert isinstance(good.result(), Completed)   # engine still healthy
    assert server.metrics()["failed"] == 0


def test_temperature_sampling_runs():
    cfg = get_smoke("mamba2-1.3b")
    params = lm.init_params(cfg, jax.random.key(2))
    eng = ServeEngine(cfg, params, max_len=32)
    rng = np.random.default_rng(2)
    p = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    outs = eng.generate([Request(p, max_new_tokens=5, temperature=1.0)],
                        seed=3)
    assert outs[0].shape == (5,)


def _gnn_launcher_args(**over):
    import argparse
    args = dict(graphs="cora", models="gcn", backend="reference", layers=2,
                hidden=8, heads=2, mesh=0, partition="contiguous",
                hub_cache=0, model_parallel=1, plan="analytic",
                tune_budget=1, shard_n=64, scale=0.05, nodes_per_req=4,
                deadline_ms=None, num_requests=4, batch_size=4,
                max_wait_ms=0.0, queue_depth=16)
    args.update(over)
    return argparse.Namespace(**args)


def test_gnn_launcher_exit_status_follows_tickets(capsys):
    """The GNN serve launcher returns normally only when every ticket
    completed; expired tickets (deadline 0) make it exit non-zero."""
    from repro.launch import serve
    serve._serve_gnn(_gnn_launcher_args())
    assert "served 4/4" in capsys.readouterr().out
    with pytest.raises(SystemExit, match="4 of 4 requests were not "
                                         "completed"):
        serve._serve_gnn(_gnn_launcher_args(deadline_ms=0.0))
