"""End-to-end GNN training over compiled Executables (`runtime.fit`).

The same engine/kernel split the serving path exercises once per request —
dense feature extraction + sparse aggregation — is what a training step
exercises twice (forward and backward). Every kernel backend is
differentiable (the Pallas kernels carry oracle-derived ``custom_vjp``s,
the jax/reference backends are ad-traceable jnp), so training reuses the
exact compiled artifact serving runs on:

    result = runtime.fit(spec, graph, steps=200, backend="reference")
    result.executable.predict([0, 7, 9])     # serves the trained weights

:class:`TrainableExecutable` wraps one compiled
:class:`~repro.runtime.executable.Executable` (single-device or a
``mesh=`` :class:`~repro.dist.gnn.ShardedExecutable`) with a jitted
AdamW train step in two regimes:

  * **full-batch** — masked cross-entropy over the full-graph forward;
    on a mesh the gradient's data-parallel psum falls out of the
    ``shard_map`` transpose (all-gather -> reduce-scatter), measurable
    via :meth:`TrainableExecutable.train_comm_stats`.
  * **mini-batch** — a :class:`~repro.graphs.sampler.NeighborSampler`
    draws fixed-budget subgraphs; each is sharded to the same (S, n)
    grid and padded to one edge cap, so the step function traces once
    and every step reuses the jit.

The loop itself is :class:`~repro.training.train_loop.TrainLoop` — the
same fault-tolerant machinery LM training uses: periodic + preemption
checkpoints through :class:`~repro.checkpoint.manager.CheckpointManager`,
deterministic resume (the sampler is seeded by step), straggler logging.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.core.engines import GraphTensors, upload_edge_tiles, upload_grid
from repro.core.sharding import shard_graph
from repro.gnn.models import EDGE_SOFTMAX_ARCHS, ZooSpec, graph_signature
from repro.graphs.sampler import NeighborSampler, SubgraphBatch
from repro.runtime import forward as _fwd
from repro.runtime.executable import (Executable, _flatten_params,
                                      _unflatten_params)
from repro.training.optimizer import (AdamWConfig, adamw_init, adamw_update,
                                      make_schedule)


def masked_cross_entropy(logits: jax.Array, labels: jax.Array,
                         mask: jax.Array) -> jax.Array:
    """Mean CE over ``mask``-selected nodes (f32, mask-weighted)."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    m = mask.astype(jnp.float32)
    return jnp.sum(nll * m) / jnp.maximum(jnp.sum(m), 1.0)


def _masked_accuracy(logits, labels, mask):
    m = mask.astype(jnp.float32)
    hit = (jnp.argmax(logits, axis=-1) == labels).astype(jnp.float32)
    return jnp.sum(hit * m) / jnp.maximum(jnp.sum(m), 1.0)


def _loss(logits, labels, mask):
    with jax.named_scope("loss"):
        return masked_cross_entropy(logits, labels, mask)


def _grad_and_update(loss_fn, params, opt_state, labels, mask, opt_cfg,
                     schedule):
    """The body both train steps share: value and gradient of
    ``loss_fn`` (which returns ``(loss, logits)``), then one AdamW update.
    Returns ``(params, opt_state, metrics)``."""
    (loss, logits), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params)
    with jax.named_scope("adamw"):
        params, opt_state, stats = adamw_update(
            grads, opt_state, params, opt_cfg, schedule)
    metrics = {"loss": loss, "acc": _masked_accuracy(logits, labels, mask),
               **stats}
    return params, opt_state, metrics


def _pad_axis(x: np.ndarray, size: int, axis: int) -> np.ndarray:
    pad = size - x.shape[axis]
    if pad < 0:
        raise ValueError(f"cannot pad axis {axis} of {x.shape} to {size}")
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths)


class TrainableExecutable:
    """A compiled Executable plus the jitted train step that updates it.

    Functional core (``step_fn(params, opt_state, batch)``), stateful
    shell (``run()`` threads params/opt_state through
    :class:`~repro.training.train_loop.TrainLoop` and leaves the trained
    weights hot-swapped into ``self.executable``).
    """

    def __init__(self, exe: Executable, labels: np.ndarray, *,
                 train_mask: np.ndarray | None = None,
                 features: np.ndarray | None = None,
                 opt_cfg: AdamWConfig | None = None,
                 sampler: NeighborSampler | None = None):
        if exe._h_grouped is None and features is None:
            raise ValueError("training needs features: compile with a "
                             "featureful graph or pass features=")
        self.executable = exe
        self.spec: ZooSpec = exe.spec
        self.opt_cfg = opt_cfg or AdamWConfig(
            lr=5e-3, weight_decay=0.0, grad_clip=0.0, schedule="constant",
            warmup_steps=0)
        self._schedule = make_schedule(self.opt_cfg)
        # the jitted step DONATES its params argument; train on a copy so
        # step 0 can never invalidate the Executable's own buffers (an
        # exception mid-fit would otherwise leave exe.params deleted and
        # the compiled unit unusable)
        self.params = jax.tree.map(jnp.array, exe.params)
        self.opt_state = adamw_init(self.params)
        self.sampler = sampler

        n = exe.gt.num_nodes
        labels = np.asarray(labels)
        if labels.shape[0] != n:
            raise ValueError(f"labels cover {labels.shape[0]} nodes, graph "
                             f"has {n}")
        self._labels = np.asarray(labels, dtype=np.int32)
        self._train_mask = (np.ones(n, dtype=bool) if train_mask is None
                            else np.asarray(train_mask, dtype=bool))
        self._features = features
        if sampler is None:
            h = exe._h_grouped if exe._h_grouped is not None \
                else exe.gt.group(jnp.asarray(features))
            fwd, graph_args = exe._forward_with_args()
            self._full_batch = (h, jnp.asarray(self._labels),
                                jnp.asarray(self._train_mask), *graph_args)
            self._jit_step = jax.jit(self._make_full_step(fwd),
                                     donate_argnums=(0, 1))
        else:
            if getattr(exe, "mesh", None) is not None:
                raise NotImplementedError(
                    "mini-batch training is single-device; mesh training "
                    "runs full-batch (the sampled subgraph is already the "
                    "parallelism unit)")
            if features is None:
                raise ValueError("mini-batch training needs raw features= "
                                 "(the compiled h_grouped covers the full "
                                 "graph, not sampled subgraphs)")
            self._features = np.asarray(features, dtype=np.float32)
            self._mb = self._make_minibatch_builder()
            self._jit_step = jax.jit(self._make_mini_step(),
                                     donate_argnums=(0, 1))

    # -- step construction -------------------------------------------------

    def _make_full_step(self, fwd: Callable) -> Callable:
        """``fwd`` is :meth:`Executable._forward_with_args`'s function; the
        graph arrays arrive as trailing step arguments."""
        opt_cfg, schedule = self.opt_cfg, self._schedule

        def gnn_train_step(params, opt_state, h, labels, mask, *graph):
            def loss_fn(p):
                logits = fwd(p, h, *graph)
                return _loss(logits, labels, mask), logits

            return _grad_and_update(loss_fn, params, opt_state, labels,
                                    mask, opt_cfg, schedule)

        return gnn_train_step

    def _make_minibatch_builder(self) -> Callable:
        """numpy side of the mini-batch path: sample -> shard -> pad to
        the fixed (S, n, E_cap) template so one jit trace serves every
        step."""
        from repro.gnn.executor import plan_model

        exe, smp = self.executable, self.sampler
        norm, loops = graph_signature(self.spec.arch)
        budget = smp.budget
        est_edges = min(smp.edge_cap, budget * max(smp.fanout))
        plan = plan_model(self.spec, budget, est_edges,
                          max_n=min(exe.gt.n, budget))
        self.minibatch_plan = plan
        n_sub = plan.shard_n
        s_sub = -(-budget // n_sub)
        # per-pair cap: dense block bound (+n for stacked self loops) vs
        # total-unique-edge bound (+budget for the self loops shard_graph
        # appends on every slot)
        e_cap = min(n_sub * n_sub + n_sub, smp.edge_cap + budget)
        self._mb_shape = (s_sub, n_sub, e_cap)
        tiles = self.spec.arch in EDGE_SOFTMAX_ARCHS

        def build(step: int):
            batch: SubgraphBatch = smp.sample(step)
            sg = shard_graph(batch.edges, budget, n_sub,
                             add_self_loops=loops, normalize=norm)
            feats = self._features[batch.nodes] * \
                batch.node_valid[:, None].astype(np.float32)
            h = _pad_axis(feats, s_sub * n_sub, 0).reshape(s_sub, n_sub, -1)
            labels = self._labels[batch.nodes]
            mask = batch.seed_mask & self._train_mask[batch.nodes]
            arrays = (upload_grid(sg.blocks),
                      jnp.asarray(_pad_axis(sg.edge_src, e_cap, 2)),
                      jnp.asarray(_pad_axis(sg.edge_dst, e_cap, 2)),
                      jnp.asarray(_pad_axis(sg.edge_valid, e_cap, 2)),
                      jnp.asarray(h), jnp.asarray(labels),
                      jnp.asarray(mask))
            if not tiles:
                return arrays
            # one chunk count for every step: the sampler's edge bound
            # and a self loop a node
            return arrays + (upload_edge_tiles(
                sg.edge_src, sg.edge_dst, sg.edge_valid, n_sub,
                e_cap=smp.edge_cap + budget),)

        return build

    def _make_mini_step(self) -> Callable:
        spec, backend = self.spec, self.executable.backend
        opt_cfg, schedule = self.opt_cfg, self._schedule
        budget = self.sampler.budget
        s_sub, n_sub, _ = self._mb_shape
        plans = self.minibatch_plan.layers

        def gnn_train_step(params, opt_state, blocks, e_src, e_dst, e_valid,
                           h, labels, mask, tiles=None):
            gt = GraphTensors(blocks=blocks, edge_src=e_src, edge_dst=e_dst,
                              edge_valid=e_valid, num_nodes=budget,
                              n=n_sub, S=s_sub, tiles=tiles)

            def loss_fn(p):
                logits = _fwd.forward(spec, p, gt, h, plans=plans,
                                      backend=backend)
                return _loss(logits, labels, mask), logits

            return _grad_and_update(loss_fn, params, opt_state, labels,
                                    mask, opt_cfg, schedule)

        return gnn_train_step

    def update_sampler(self, sampler: NeighborSampler, *,
                       features: np.ndarray | None = None,
                       labels: np.ndarray | None = None,
                       train_mask: np.ndarray | None = None) -> None:
        """Swap the neighbor sampler (and optionally the raw node data)
        WITHOUT retracing the train step — the streaming fine-tune
        contract: each round re-aims sampling at the freshly-mutated
        neighborhoods while the jitted step keeps its one trace.

        The new sampler must produce the compiled mini-batch template:
        same ``budget``, ``batch_nodes`` and ``fanout`` (pass
        ``budget=old.budget`` explicitly when the graph grew — the
        default derivation clamps at num_nodes). Raises ValueError on
        any template change; the swap is all-or-nothing."""
        if self.sampler is None:
            raise ValueError("update_sampler requires mini-batch mode "
                             "(constructed with sampler=)")
        old = self.sampler
        if (sampler.budget != old.budget
                or sampler.batch_nodes != old.batch_nodes
                or tuple(sampler.fanout) != tuple(old.fanout)):
            raise ValueError(
                f"sampler template mismatch: compiled (budget="
                f"{old.budget}, batch_nodes={old.batch_nodes}, fanout="
                f"{old.fanout}), got (budget={sampler.budget}, "
                f"batch_nodes={sampler.batch_nodes}, fanout="
                f"{sampler.fanout}) — a changed template needs a new "
                f"TrainableExecutable")
        prev = (self.sampler, self._features, self._labels,
                self._train_mask, self._mb, self._mb_shape,
                self.minibatch_plan)
        try:
            self.sampler = sampler
            if features is not None:
                self._features = np.asarray(features, dtype=np.float32)
            if labels is not None:
                self._labels = np.asarray(labels, dtype=np.int32)
            if train_mask is not None:
                self._train_mask = np.asarray(train_mask, dtype=bool)
            shape_before = self._mb_shape
            self._mb = self._make_minibatch_builder()
            if self._mb_shape != shape_before:
                raise ValueError(
                    f"mini-batch template changed {shape_before} -> "
                    f"{self._mb_shape}; rebuild the TrainableExecutable")
        except Exception:
            (self.sampler, self._features, self._labels, self._train_mask,
             self._mb, self._mb_shape, self.minibatch_plan) = prev
            raise
        # self._jit_step deliberately kept: same (S, n, E_cap) template
        # => the existing trace serves every post-swap step

    # -- TrainLoop protocol ------------------------------------------------

    def data(self, step: int):
        """Step-indexable batch (deterministic => resume-safe)."""
        if self.sampler is None:
            return self._full_batch
        return self._mb(step)

    @functools.partial(jax.profiler.annotate_function,
                       name="gnn.train_step")
    def step_fn(self, params, opt_state, batch):
        return self._jit_step(params, opt_state, *batch)

    def run(self, steps: int, *, ckpt_manager=None, ckpt_every: int = 50,
            log_every: int = 25,
            log: Callable[[str], None] = print) -> list:
        """Train to ``steps`` total (resuming from ``ckpt_manager`` if it
        holds a checkpoint), hot-swap the trained weights into the
        Executable, and return the ``(step, loss)`` history."""
        from repro.training.train_loop import TrainLoop

        loop = TrainLoop(cfg=None, opt_cfg=self.opt_cfg, data_iter=self.data,
                         ckpt_manager=ckpt_manager, ckpt_every=ckpt_every,
                         log_every=log_every)
        self.params, self.opt_state, history = loop.run(
            self.params, self.opt_state, steps, train_step=self.step_fn,
            log=log)
        if ckpt_manager is not None:
            ckpt_manager.wait()
        self.executable.update_params(self.params)
        return history

    # -- evaluation / state ------------------------------------------------

    def train_accuracy(self, params=None) -> float:
        """Full-graph accuracy over the train mask (current params)."""
        p = self.params if params is None else params
        logits = self.executable.forward(
            p, features=None if self._features is None
            or self.executable._h_grouped is not None else self._features)
        return float(_masked_accuracy(jnp.asarray(logits),
                                      jnp.asarray(self._labels),
                                      jnp.asarray(self._train_mask)))

    def state_dict(self) -> dict:
        """The resumable train state as one pytree."""
        return {"params": self.params, "opt": self.opt_state}

    def save_state(self, path) -> None:
        """npz snapshot of params + optimizer state (flat pytree keys —
        the same layout ``Executable.save_params`` uses)."""
        np.savez(path, **_flatten_params(self.state_dict()))

    def load_state(self, path) -> dict:
        with np.load(path) as z:
            state = _unflatten_params(dict(z))
        self.params = state["params"]
        opt = state["opt"]
        opt["step"] = jnp.asarray(opt["step"], jnp.int32)
        self.opt_state = opt
        self.executable.update_params(self.params)
        return state

    # -- distributed accounting --------------------------------------------

    def train_comm_stats(self) -> dict:
        """Collective traffic of the compiled TRAIN step (mesh runs only):
        per-kind wire bytes/counts from the HLO, next to the forward
        all-gather model — the backward pass adds the all-gather
        transposes (reduce-scatter) and the data-parallel gradient psum
        (all-reduce over replicated params)."""
        from repro.dist.hlo_analysis import analyze_collectives

        exe = self.executable
        if getattr(exe, "mesh", None) is None:
            raise ValueError("train_comm_stats needs a mesh-compiled "
                             "Executable (runtime.fit(..., mesh=...))")
        aval = lambda x: jax.ShapeDtypeStruct(jnp.shape(x),
                                              jnp.result_type(x))
        # the graph arrays keep their mesh placement, so the lowered step
        # is the one that runs
        placed = lambda x: jax.ShapeDtypeStruct(
            jnp.shape(x), jnp.result_type(x),
            sharding=x.sharding if isinstance(x.sharding, NamedSharding)
            else None)
        args = (jax.tree.map(aval, self.params),
                jax.tree.map(aval, self.opt_state),
                *(placed(b) for b in self._full_batch))
        hlo = self._jit_step.lower(*args).compile().as_text()
        stats = analyze_collectives(hlo)
        return {
            "measured_wire_bytes": dict(stats.wire_bytes),
            "measured_counts": dict(stats.counts),
            "forward_allgather_wire_bytes":
                sum(exe._layer_allgather_bytes()),
            "n_data": exe.n_data,
            "n_model": exe.n_model,
        }

    def verify_train_comm(self) -> dict:
        """Assert the train step's measured collectives are consistent
        with the forward model: at least the forward all-gather volume on
        the wire, plus a reduction collective carrying the data-parallel
        gradient psum. Returns :meth:`train_comm_stats`."""
        cs = self.train_comm_stats()
        measured_ag = cs["measured_wire_bytes"].get("all-gather", 0.0)
        expected_fwd = cs["forward_allgather_wire_bytes"]
        assert measured_ag >= 0.98 * expected_fwd, (measured_ag, expected_fwd)
        if cs["n_data"] * cs["n_model"] > 1:
            reduces = sum(cs["measured_counts"].get(k, 0)
                          for k in ("all-reduce", "reduce-scatter"))
            assert reduces > 0, cs["measured_counts"]
        return cs


@dataclasses.dataclass
class FitResult:
    """What :func:`fit` hands back: the trained, servable Executable plus
    the functional train state and loss history."""

    executable: Executable
    trainable: TrainableExecutable
    params: dict
    opt_state: dict
    history: list          # (step, loss) at log_every cadence

    def train_accuracy(self) -> float:
        return self.trainable.train_accuracy()


def fit(spec: ZooSpec, graph, labels=None, *,
        train_mask=None, steps: int = 100,
        opt: AdamWConfig | None = None, lr: float = 5e-3,
        weight_decay: float = 0.0, grad_clip: float = 0.0,
        schedule: str = "constant", warmup_steps: int = 0,
        batch_nodes: int = 0, fanout: Sequence[int] = (10, 5),
        backend=None, mesh=None, partition: str = "contiguous",
        hub_cache: int = 256, max_shard_n: int = 1024,
        plan: str = "analytic", tune_budget: int = 16,
        params: dict | None = None, seed: int = 0, store=None,
        ckpt_manager=None, ckpt_dir=None, ckpt_every: int = 50,
        log_every: int = 25, log: Callable[[str], None] = print
        ) -> FitResult:
    """Compile one zoo model and train it end to end.

    Args:
      spec: the :class:`~repro.gnn.models.ZooSpec` to train.
      graph: a :class:`~repro.graphs.datasets.GraphData` (labels and
        train_mask default from it) or ``(edges, num_nodes, features)``.
      labels: (N,) int class labels; required for tuple graphs.
      train_mask: (N,) bool loss mask; default: GraphData.train_mask, or
        every node.
      steps: TOTAL optimization steps — resuming from a checkpoint at k
        continues to ``steps``, exactly like an uninterrupted run.
      batch_nodes: 0 trains full-batch; > 0 neighbor-samples mini-batches
        of this many seed nodes with per-layer ``fanout``.
      mesh: a ``(data, model)`` mesh — full-batch data-parallel training
        over the sharded forward (gradient psum via the shard_map
        transpose).
      partition / hub_cache: data-axis placement for mesh training —
        see :func:`runtime.compile` (``"fennel"`` trains through the
        permuted row groups + hub cache; gradients flow through the
        inverse permutation).
      ckpt_manager / ckpt_dir: resume + periodic checkpointing through
        :class:`~repro.checkpoint.manager.CheckpointManager`.

    Everything else matches :func:`runtime.compile`.
    """
    from repro import runtime

    if hasattr(graph, "profile"):
        if labels is None:
            labels = graph.labels
        if train_mask is None:
            train_mask = graph.train_mask
        features = graph.features
    else:
        edges, num_nodes, features = runtime.api._as_graph(graph)
        if features is None:
            raise ValueError("training needs node features")
    if labels is None:
        raise ValueError("training needs labels (pass labels= or a "
                         "GraphData)")

    exe = runtime.compile(spec, graph, backend=backend, mesh=mesh,
                          partition=partition, hub_cache=hub_cache,
                          max_shard_n=max_shard_n, params=params,
                          plan=plan, tune_budget=tune_budget,
                          seed=seed, store=store)
    opt_cfg = opt or AdamWConfig(
        lr=lr, weight_decay=weight_decay, grad_clip=grad_clip,
        schedule=schedule, warmup_steps=warmup_steps, total_steps=steps)

    sampler = None
    if batch_nodes:
        tm = np.asarray(train_mask, dtype=bool) if train_mask is not None \
            else np.ones(exe.gt.num_nodes, dtype=bool)
        seed_ids = np.flatnonzero(tm)
        edges_np = graph.edges if hasattr(graph, "profile") else \
            np.asarray(graph[0])
        sampler = NeighborSampler(
            edges_np, exe.gt.num_nodes, batch_nodes=batch_nodes,
            fanout=tuple(fanout), seed_ids=seed_ids, seed=seed)

    trainable = TrainableExecutable(
        exe, labels, train_mask=train_mask,
        features=np.asarray(features, dtype=np.float32),
        opt_cfg=opt_cfg, sampler=sampler)

    if ckpt_manager is None and ckpt_dir is not None:
        from repro.checkpoint.manager import CheckpointManager
        ckpt_manager = CheckpointManager(str(ckpt_dir), keep=3)

    history = trainable.run(steps, ckpt_manager=ckpt_manager,
                            ckpt_every=ckpt_every, log_every=log_every,
                            log=log)
    return FitResult(executable=exe, trainable=trainable,
                     params=trainable.params, opt_state=trainable.opt_state,
                     history=history)
