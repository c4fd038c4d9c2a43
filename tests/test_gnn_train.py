"""End-to-end GNN training (`runtime.fit`): accuracy on cora, mini-batch
sampling, checkpoint/resume determinism, and hot reload of trained
weights into the compiled Executable."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import runtime
from repro.checkpoint.manager import CheckpointManager
from repro.gnn.models import ZooSpec
from repro.graphs.datasets import make_dataset
from repro.graphs.sampler import NeighborSampler
from repro.runtime.executable import _flatten_params, _unflatten_params


def _bitwise_equal(tree_a, tree_b) -> bool:
    la, lb = jax.tree.leaves(tree_a), jax.tree.leaves(tree_b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(la, lb))


class TestFitAccuracy:
    @pytest.mark.parametrize("arch", ["gcn", "sage_mean", "gin"])
    def test_trains_cora_to_accuracy(self, arch):
        """The acceptance gate: >= 0.75 train accuracy on (synthetic)
        cora within 200 full-batch steps on the reference backend."""
        ds = make_dataset("cora", seed=0)
        spec = ZooSpec(arch, ds.profile.feature_dim, 16,
                       ds.profile.num_classes)
        res = runtime.fit(spec, ds, steps=150, lr=1e-2,
                          backend="reference", log=lambda s: None)
        acc = res.train_accuracy()
        assert acc >= 0.75, f"{arch}: train acc {acc:.3f} < 0.75"
        # losses monotone-ish: end well below start
        assert res.history[-1][1] < 0.7 * res.history[0][1]
        # the trained weights were hot-swapped into the Executable
        assert _bitwise_equal(res.executable.params, res.params)
        classes, probs = res.executable.predict([0, 1, 2])
        assert classes.shape == (3,)

    def test_gat_fits_through_the_edge_softmax_kernel(self):
        """Full-batch steps of GAT (8 hidden heads, 2 averaged output
        heads) on the pallas backend: the forward runs the edge softmax
        kernel, the backward its oracle, and the loss falls."""
        ds = make_dataset("cora", seed=0, scale=0.1)
        spec = ZooSpec("gat", ds.profile.feature_dim, 16,
                       ds.profile.num_classes, heads=8, out_heads=2)
        res = runtime.fit(spec, ds, steps=4, lr=1e-2, backend="pallas",
                          max_shard_n=128, log_every=1, log=lambda s: None)
        losses = [loss for _, loss in res.history]
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

    def test_fit_requires_labels_and_features(self):
        ds = make_dataset("cora", seed=0, scale=0.1)
        spec = ZooSpec("gcn", ds.profile.feature_dim, 8,
                       ds.profile.num_classes)
        with pytest.raises(ValueError, match="labels"):
            runtime.fit(spec, (ds.edges, ds.profile.num_nodes, ds.features),
                        steps=1, backend="reference", log=lambda s: None)
        with pytest.raises(ValueError, match="features"):
            runtime.fit(spec, (ds.edges, ds.profile.num_nodes),
                        labels=ds.labels, steps=1, backend="reference",
                        log=lambda s: None)


class TestMiniBatch:
    def test_sampler_is_deterministic_and_budgeted(self):
        ds = make_dataset("citeseer", seed=0, scale=0.3)
        smp = NeighborSampler(ds.edges, ds.profile.num_nodes,
                              batch_nodes=16, fanout=(4, 3), seed=7)
        a, b = smp.sample(5), smp.sample(5)
        assert np.array_equal(a.nodes, b.nodes)
        assert np.array_equal(a.edges, b.edges)
        c = smp.sample(6)
        assert not np.array_equal(a.nodes, c.nodes)
        # fixed shapes: budget-sized node set, seeds first
        assert a.nodes.shape == (smp.budget,)
        assert a.seed_mask[:16].all() or a.seed_mask.sum() <= 16
        assert a.edges.shape[0] <= smp.edge_cap
        # every edge endpoint is a real (non-padding) local id
        if a.edges.size:
            assert a.edges.max() < a.num_real

    def test_sampler_handles_zero_in_degree_tail_nodes(self):
        """A frontier node whose CSR offset sits at E (no in-edges, all
        edge dsts below its id) used to read past src_sorted before the
        validity mask applied — IndexError on real training data."""
        edges = np.array([[0, 1]], dtype=np.int64)
        smp = NeighborSampler(edges, 3, batch_nodes=3, fanout=(2,), seed=0)
        batch = smp.sample(0)               # must not raise
        assert batch.num_real >= 1
        # edge-free graph is fine too
        empty = NeighborSampler(np.empty((0, 2), np.int64), 4,
                                batch_nodes=2, fanout=(2,))
        assert empty.sample(0).edges.shape[0] == 0

    def test_sampler_dedupes_seeds_when_pool_is_small(self):
        """batch_nodes > |seed pool| draws with replacement; duplicate
        seeds must collapse to one local slot each (a duplicate slot
        would sit in the loss mask with no in-edges)."""
        ds = make_dataset("cora", seed=0, scale=0.1)
        pool = np.arange(4, dtype=np.int64)
        smp = NeighborSampler(ds.edges, ds.profile.num_nodes,
                              batch_nodes=16, fanout=(3,), seed_ids=pool)
        batch = smp.sample(0)
        n_seeds = int(batch.seed_mask.sum())
        assert n_seeds <= pool.size
        seeds = batch.nodes[:n_seeds]
        assert len(np.unique(seeds)) == n_seeds

    def test_minibatch_fit_learns(self):
        ds = make_dataset("cora", seed=0, scale=0.5)
        spec = ZooSpec("gcn", ds.profile.feature_dim, 16,
                       ds.profile.num_classes)
        res = runtime.fit(spec, ds, steps=30, lr=1e-2, batch_nodes=64,
                          fanout=(5, 5), backend="reference",
                          log=lambda s: None)
        assert np.isfinite(res.history[-1][1])
        assert res.history[-1][1] < res.history[0][1]

    def test_minibatch_rejects_mesh(self):
        ds = make_dataset("cora", seed=0, scale=0.1)
        spec = ZooSpec("gcn", ds.profile.feature_dim, 8,
                       ds.profile.num_classes)
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for(1, model_parallel=1)
        with pytest.raises(NotImplementedError, match="mini-batch"):
            runtime.fit(spec, ds, steps=1, batch_nodes=8, mesh=mesh,
                        backend="reference", log=lambda s: None)


class TestCheckpointResume:
    def test_resume_is_bitwise_deterministic(self, tmp_path):
        """Train k steps, checkpoint, resume in a fresh fit run: params
        AND optimizer state must be bitwise equal to an uninterrupted
        run of the same total length."""
        ds = make_dataset("cora", seed=0, scale=0.2)
        spec = ZooSpec("gcn", ds.profile.feature_dim, 8,
                       ds.profile.num_classes)
        kw = dict(backend="reference", log=lambda s: None)

        uninterrupted = runtime.fit(spec, ds, steps=8, **kw)

        d = str(tmp_path / "ckpt")
        runtime.fit(spec, ds, steps=4, ckpt_manager=CheckpointManager(d),
                    ckpt_every=4, **kw)
        resumed = runtime.fit(spec, ds, steps=8,
                              ckpt_manager=CheckpointManager(d),
                              ckpt_every=100, **kw)

        assert _bitwise_equal(uninterrupted.params, resumed.params)
        assert _bitwise_equal(uninterrupted.opt_state, resumed.opt_state)
        assert int(resumed.opt_state["step"]) == 8

    def test_minibatch_resume_replays_sampler(self, tmp_path):
        """The sampler is seeded by step, so a resumed mini-batch run
        sees the exact batches the uninterrupted run saw."""
        ds = make_dataset("cora", seed=0, scale=0.2)
        spec = ZooSpec("gcn", ds.profile.feature_dim, 8,
                       ds.profile.num_classes)
        kw = dict(backend="reference", batch_nodes=16, fanout=(4,),
                  log=lambda s: None)

        uninterrupted = runtime.fit(spec, ds, steps=6, **kw)
        d = str(tmp_path / "ckpt")
        runtime.fit(spec, ds, steps=3, ckpt_manager=CheckpointManager(d),
                    ckpt_every=3, **kw)
        resumed = runtime.fit(spec, ds, steps=6,
                              ckpt_manager=CheckpointManager(d),
                              ckpt_every=100, **kw)
        assert _bitwise_equal(uninterrupted.params, resumed.params)
        assert _bitwise_equal(uninterrupted.opt_state, resumed.opt_state)

    def test_unflatten_roundtrips_optimizer_state_trees(self):
        """_unflatten_params must rebuild the full train state — params
        lists AND the mirrored optimizer moment trees + scalar step."""
        from repro.training.optimizer import adamw_init

        spec = ZooSpec("gin", 6, 8, 3)
        from repro.gnn.models import init_zoo
        params = init_zoo(jax.random.key(0), spec)
        state = {"params": params, "opt": adamw_init(params)}
        state["opt"]["step"] = jnp.asarray(5, jnp.int32)

        rebuilt = _unflatten_params(_flatten_params(state))
        assert _bitwise_equal(state, rebuilt)
        assert isinstance(rebuilt["params"]["layers"], list)
        assert isinstance(rebuilt["opt"]["m"]["layers"], list)
        assert int(rebuilt["opt"]["step"]) == 5

    def test_save_load_state_roundtrip(self, tmp_path):
        ds = make_dataset("cora", seed=0, scale=0.15)
        spec = ZooSpec("gcn", ds.profile.feature_dim, 8,
                       ds.profile.num_classes)
        res = runtime.fit(spec, ds, steps=3, backend="reference",
                          log=lambda s: None)
        path = tmp_path / "state.npz"
        res.trainable.save_state(path)

        fresh = runtime.fit(spec, ds, steps=0, backend="reference",
                            log=lambda s: None)
        state = fresh.trainable.load_state(path)
        assert _bitwise_equal(state["params"], res.params)
        assert _bitwise_equal(fresh.trainable.opt_state, res.opt_state)
        # the reload propagated into the wrapped Executable
        assert _bitwise_equal(fresh.executable.params, res.params)


class TestHotReloadExecutable:
    def test_update_params_validates_and_invalidates_once(self):
        ds = make_dataset("cora", seed=0, scale=0.15)
        spec = ZooSpec("gcn", ds.profile.feature_dim, 8,
                       ds.profile.num_classes)
        exe = runtime.compile(spec, ds, backend="reference")
        exe.predict([0, 1])
        assert exe.has_cached_probs

        from repro.gnn.models import init_zoo
        exe.update_params(init_zoo(jax.random.key(9), spec))
        assert not exe.has_cached_probs        # invalidated by the swap

        bad_spec = ZooSpec("gcn", ds.profile.feature_dim, 12,
                           ds.profile.num_classes)
        with pytest.raises(ValueError, match="shape"):
            exe.update_params(init_zoo(jax.random.key(0), bad_spec))
        with pytest.raises(ValueError, match="tree"):
            exe.update_params(
                init_zoo(jax.random.key(0),
                         ZooSpec("gin", ds.profile.feature_dim, 8,
                                 ds.profile.num_classes)))
