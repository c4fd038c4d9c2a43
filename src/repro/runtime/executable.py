"""The compiled unit the runtime hands back: plan + graph + params + jit.

An :class:`Executable` owns everything needed to run one zoo model on one
graph on one kernel backend:

  * the :class:`~repro.gnn.executor.ModelPlan` (content-hash memoized by
    the planner),
  * the signature-keyed :class:`~repro.core.engines.GraphTensors` build
    (shared across Executables via the runtime GraphStore),
  * a jitted forward — full-graph (`forward`) and node-batch
    (`forward_nodes` / `predict`) entry points; the node-batch path is
    answered from a cached full-graph softmax, the natural unit of work on
    the accelerator (one shard-grid sweep per layer covers every node),
  * plan/param serialization (`save_plan`, `save_params`, `load_params`).

The kernel backend is pinned at compile time: later changes to the
``REPRO_KERNEL_BACKEND`` env var do not retroactively re-route a compiled
Executable.
"""
from __future__ import annotations

import functools
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.engines import GraphTensors
from repro.gnn.executor import ModelPlan
from repro.gnn.models import ZooSpec
from repro.kernels.registry import KernelBackend
from repro.runtime import forward as _fwd


def _softmax(x: np.ndarray) -> np.ndarray:
    x = x - x.max(axis=-1, keepdims=True)
    e = np.exp(x)
    return e / e.sum(axis=-1, keepdims=True)


def _flatten_params(tree, prefix="", out=None) -> dict:
    if out is None:
        out = {}
    if isinstance(tree, dict):
        for k, v in tree.items():
            _flatten_params(v, f"{prefix}{k}/", out)
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            _flatten_params(v, f"{prefix}{i}/", out)
    else:
        out[prefix[:-1]] = np.asarray(tree)
    return out


def validate_params_like(old, new) -> None:
    """Raise ValueError unless ``new`` has the same pytree structure and
    per-leaf shapes as ``old`` — the hot-reload contract (same shapes =>
    existing jit traces keep serving). Shared by
    :meth:`Executable.update_params` and the serving engine's
    all-or-nothing reload pre-check."""
    old_leaves, old_def = jax.tree_util.tree_flatten(old)
    new_leaves, new_def = jax.tree_util.tree_flatten(new)
    if old_def != new_def:
        raise ValueError(
            f"param tree mismatch: compiled {old_def}, got {new_def}")
    for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
        if jnp.shape(o) != jnp.shape(n):
            raise ValueError(
                f"param leaf {i} shape mismatch: compiled "
                f"{jnp.shape(o)}, got {jnp.shape(n)}")


def _unflatten_params(flat: dict):
    root: dict = {}
    for key, val in flat.items():
        node = root
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = jnp.asarray(val)

    def listify(node):
        if not isinstance(node, dict):
            return node
        if node and all(k.isdigit() for k in node):
            # index-robust: a pruned/partial checkpoint may hold
            # non-contiguous digit keys ("0", "2"); rebuild the list from
            # the keys actually present, in numeric order, instead of
            # assuming 0..len-1 (which KeyError'd on any gap)
            return [listify(node[k]) for k in sorted(node, key=int)]
        return {k: listify(v) for k, v in node.items()}

    return listify(root)


class Executable:
    """A zoo model compiled against one graph, plan and kernel backend."""

    def __init__(self, *, spec: ZooSpec, plan: ModelPlan,
                 backend: KernelBackend, gt: GraphTensors,
                 h_grouped: jax.Array | None, params: dict,
                 graph_key=None, donate_features: bool = False,
                 plan_source: str = "analytic",
                 tune_report: dict | None = None):
        self.spec = spec
        self.plan = plan
        self.backend = backend
        self.gt = gt
        self.params = params
        self.graph_key = graph_key
        # where the plan came from ("analytic" | "autotune" |
        # "analytic_fallback") and, for tuned plans, the measurement
        # evidence (winner vs analytic ms, candidates tried) — surfaced by
        # summary() so a serving operator can see WHY this config runs
        self.plan_source = plan_source
        self.tune_report = tune_report
        # static-analysis Report, populated by runtime.compile(analyze=...)
        self.analysis = None
        # monotonic version of the graph this Executable currently serves
        # (bumped by update_graph via the serving engine's mutate path)
        self.graph_version = 0
        self._h_grouped = h_grouped
        self._probs: np.ndarray | None = None
        # per-row staleness of the cached softmax under targeted graph
        # invalidation; None = every cached row fresh
        self._stale: np.ndarray | None = None

        fwd = self._forward_graph_fn()
        # graph tensors are jit ARGUMENTS, not closure constants: a
        # streaming delta that keeps the (S, S, n, n)/(S, S, E) template
        # swaps the arrays without retracing (the RT003 mutation oracle
        # in repro.analyze holds this line)
        self._jit_forward = jax.jit(fwd)
        # the donated variant consumes the caller's fresh feature buffer so
        # XLA can reuse it for layer intermediates; only sound for features
        # passed per call (the cached buffer must survive repeat calls)
        self._jit_forward_donate = (
            jax.jit(fwd, donate_argnums=(1,)) if donate_features else None)
        # node-batch gather, jitted over PADDED id vectors: ids arrive
        # bucketed to a power of two (`_gather_bucket`), so arbitrary batch
        # sizes share O(log max_batch) traces instead of one per distinct
        # shape (the per-request dispatch-compile the retrace pass flags).
        # A function of its own per Executable, so that the jit cache the
        # retrace pass counts holds this unit's traces only.
        def gnn_node_gather(logits, ids):
            return logits[ids]

        self._jit_gather = jax.jit(gnn_node_gather)

    @staticmethod
    def _graph_args_of(gt: GraphTensors) -> tuple:
        """The graph arrays passed as jit arguments (the mutable part of
        the forward's signature; S/n stay trace constants)."""
        return (gt.blocks, gt.edge_src, gt.edge_dst, gt.edge_valid)

    def _graph_args(self) -> tuple:
        return self._graph_args_of(self.gt)

    def _forward_graph_fn(self):
        """(params, h_grouped, *graph_args) -> (S·n, C) PADDED logits —
        the function jitted at construction. The graph tensors enter as
        arguments and ``num_nodes`` is fixed at the padded S·n inside the
        trace, so both edge churn and node growth within the padding stay
        in-template; the true-N slice happens OUTSIDE jit (`forward`).
        Subclasses (dist.gnn.ShardedExecutable) override this to run the
        same plan under shard_map."""
        spec, plan, backend = self.spec, self.plan, self.backend
        n, S = self.gt.n, self.gt.S

        # the function's name names the jitted program (``jit_gnn_forward``)
        def gnn_forward(p, h, blocks, e_src, e_dst, e_valid):
            g = GraphTensors(blocks=blocks, edge_src=e_src, edge_dst=e_dst,
                             edge_valid=e_valid, num_nodes=S * n, n=n, S=S)
            return _fwd.forward(spec, p, g, h, plans=plan.layers,
                                backend=backend)

        return gnn_forward

    def _forward_with_args(self):
        """``(fn, graph_args)`` with ``fn(params, h_grouped, *graph_args)
        -> (N, C)`` logits — the view a jitted train step closes over.
        The graph arrays travel as ARGUMENTS: a jit that closed over them
        would embed the (S, S, n, n) grid in its program as a constant
        (1.6 GB for full pubmed)."""
        g = self._forward_graph_fn()
        num_nodes = self.gt.num_nodes
        return (lambda p, h, *ga: g(p, h, *ga)[:num_nodes]), \
            self._graph_args()

    def _forward_fn(self):
        """2-arg view ``(params, h_grouped) -> (N, C)`` with the current
        graph bound — the shape the jaxpr static analyzer walks."""
        fn, args = self._forward_with_args()
        return lambda p, h: fn(p, h, *args)

    # -- forward entry points ---------------------------------------------

    @property
    def backend_name(self) -> str:
        return self.backend.name

    @functools.partial(jax.profiler.annotate_function, name="gnn.forward")
    def forward(self, params: dict | None = None,
                features: np.ndarray | jax.Array | None = None) -> jax.Array:
        """Full-graph logits (N, num_classes).

        ``features`` (N, F) overrides the compiled-in graph features (they
        are shard-grouped here); ``params`` overrides the compiled-in
        parameters — both stay differentiable/jit-stable, so this is also
        the training entry point.
        """
        p = self.params if params is None else params
        ga = self._graph_args()
        if features is None:
            if self._h_grouped is None:
                raise ValueError("compiled without features; pass features=")
            out = self._jit_forward(p, self._h_grouped, *ga)
        else:
            h = self.gt.group(jnp.asarray(features))
            if self._jit_forward_donate is not None:
                out = self._jit_forward_donate(p, h, *ga)
            else:
                out = self._jit_forward(p, h, *ga)
        # true-N slice OUTSIDE jit: node-count changes within the S·n
        # padding never perturb the trace
        with jax.profiler.TraceAnnotation("gnn.forward.slice"):
            return out[: self.gt.num_nodes]

    def _check_node_ids(self, node_ids) -> np.ndarray:
        """Validate ids against the compiled graph. Negative ids would
        silently wrap around (numpy/jnp indexing) and return the *wrong
        node's* prediction; ids >= N would clamp or wrap — both are data
        corruption, not errors, unless caught here."""
        ids = np.asarray(node_ids, dtype=np.int64)
        if ids.size:
            lo, hi = int(ids.min()), int(ids.max())
            if lo < 0 or hi >= self.gt.num_nodes:
                raise ValueError(
                    f"node ids must be in [0, {self.gt.num_nodes}); got "
                    f"range [{lo}, {hi}]")
        return ids

    @staticmethod
    def _gather_bucket(k: int) -> int:
        """Pad bucket for a node-batch gather: next power of two, floor 8,
        so every batch size in a bucket reuses one gather trace."""
        return max(8, 1 << max(k - 1, 0).bit_length())

    def forward_nodes(self, node_ids, params: dict | None = None) -> jax.Array:
        """Node-batch logits (k, num_classes) for ``node_ids``.

        Ids are padded to the enclosing power-of-two bucket before the
        jitted gather: without the bucket, every distinct batch size is a
        new gather shape and a new compile — the per-node-batch retrace
        hazard ``repro.analyze``'s retrace pass exists to catch.
        """
        ids = self._check_node_ids(node_ids)
        logits = self.forward(params)
        k = int(ids.size)
        if k == 0:
            return logits[:0]
        padded = np.zeros(self._gather_bucket(k), dtype=np.int32)
        padded[:k] = ids
        return self._jit_gather(logits, jnp.asarray(padded))[:k]

    def full_probs(self) -> np.ndarray:
        """Cached full-graph class probabilities (N, C); computed once per
        parameter set, then every node-batch request is a pure gather."""
        if self._probs is None:
            logits = self.forward()
            with jax.profiler.TraceAnnotation("serve.softmax"):
                # the ONE deliberate materialization point: the softmax
                # cache lives on host so every later request is a numpy
                # gather
                host = jax.device_get(logits)  # analyze: allow(host-sync)
                self._probs = _softmax(np.asarray(host, dtype=np.float32))
            self._stale = None      # one full recompute clears staleness
        return self._probs

    def predict(self, node_ids) -> tuple[np.ndarray, np.ndarray]:
        """(classes, probs) for a node batch, served from the cached
        full-graph softmax. A request touching a graph-mutation-staled
        row triggers ONE full recompute (which freshens every row);
        requests over fresh rows keep serving from the cache."""
        ids = self._check_node_ids(node_ids)
        if not self.probs_fresh_for(ids):
            self.invalidate()
        p = self.full_probs()[ids]
        return (np.argmax(p, axis=-1).astype(np.int32),
                np.max(p, axis=-1).astype(np.float32))

    def step(self, node_id_batches) -> list[tuple[np.ndarray, np.ndarray,
                                                  float]]:
        """Batch-step entry point (the serving Engine protocol's unit of
        work): answer a micro-batch of node-id queries from the cached
        full-graph softmax. Each query is timed individually — the
        full-graph forward runs at most once, on the first cold query,
        and is charged to the query that triggered it; warm queries pay
        only their gather. Returns ``(classes, probs, engine_ms)`` per
        query, positionally."""
        out = []
        for ids in node_id_batches:
            t0 = time.perf_counter()
            classes, probs = self.predict(ids)
            out.append((classes, probs, (time.perf_counter() - t0) * 1e3))
        return out

    @property
    def has_cached_probs(self) -> bool:
        return self._probs is not None

    def probs_fresh_for(self, node_ids) -> bool:
        """True iff a cached softmax exists and none of ``node_ids`` was
        staled by a targeted graph invalidation — i.e. the batch can be
        answered without a forward."""
        if self._probs is None:
            return False
        if self._stale is None:
            return True
        ids = np.asarray(node_ids, dtype=np.int64)
        return not bool(self._stale[ids].any()) if ids.size else True

    def invalidate(self) -> None:
        """Drop the cached full-graph probabilities (e.g. weight swap)."""
        self._probs = None
        self._stale = None

    def invalidate_nodes(self, node_ids) -> int:
        """Targeted invalidation: mark ``node_ids`` rows of the cached
        softmax stale instead of flushing the cache. Fresh-row requests
        keep hitting; the first stale-row request pays one full-graph
        recompute. Returns the number of NEWLY staled rows (0 when
        nothing is cached — there is nothing to invalidate)."""
        if self._probs is None:
            return 0
        ids = np.asarray(node_ids, dtype=np.int64)
        ids = np.unique(ids[(ids >= 0) & (ids < self._probs.shape[0])])
        if ids.size == 0:
            return 0
        if self._stale is None:
            self._stale = np.zeros(self._probs.shape[0], dtype=bool)
        newly = int((~self._stale[ids]).sum())
        self._stale[ids] = True
        return newly

    def update_graph(self, gt: GraphTensors,
                     h_grouped: jax.Array | None = None, *,
                     stale_nodes=None, refine_nodes=None) -> int:
        """Adopt post-delta graph tensors without recompiling.

        Every graph array must keep the compiled template (shape + dtype,
        same S/n) — the in-place streaming contract; a compaction that
        changed shapes raises ValueError and the caller must recompile.
        ``stale_nodes`` (the delta's k-hop affected set) makes the
        invalidation targeted; None, or a node-count change, flushes the
        whole softmax cache. ``refine_nodes`` is a placement re-score
        hint for subclasses that maintain a vertex placement (the fennel
        partitioner in ``dist.gnn``) — ignored here. Returns the number
        of cached rows invalidated (for the serving engine's stats)."""
        old, new = self._graph_args(), self._graph_args_of(gt)
        for name, o, nw in zip(("blocks", "edge_src", "edge_dst",
                                "edge_valid"), old, new):
            if jnp.shape(o) != jnp.shape(nw) or o.dtype != nw.dtype:
                raise ValueError(
                    f"graph template break: {name} was "
                    f"{jnp.shape(o)}/{o.dtype}, delta produced "
                    f"{jnp.shape(nw)}/{nw.dtype} (compaction?) — "
                    f"recompile required")
        if (gt.S, gt.n) != (self.gt.S, self.gt.n):
            raise ValueError(
                f"graph template break: grid {self.gt.S}x{self.gt.n} -> "
                f"{gt.S}x{gt.n} — recompile required")
        if h_grouped is not None:
            if self._h_grouped is not None and \
                    jnp.shape(h_grouped) != jnp.shape(self._h_grouped):
                raise ValueError(
                    f"feature template break: {jnp.shape(self._h_grouped)}"
                    f" -> {jnp.shape(h_grouped)} — recompile required")
            self._h_grouped = h_grouped
        grew = gt.num_nodes != self.gt.num_nodes
        self.gt = gt
        if stale_nodes is None or grew:
            rows = self._probs.shape[0] if self._probs is not None else 0
            self.invalidate()
            return rows
        return self.invalidate_nodes(stale_nodes)

    def set_params(self, params: dict) -> None:
        self.params = params
        self.invalidate()

    def update_params(self, params: dict) -> None:
        """Hot weight reload: adopt a new parameter pytree without
        recompiling. The tree structure and every leaf shape must match
        the compiled params — same shapes means the existing jit traces
        keep serving, so a reload costs one softmax recompute, not a
        compile. The cached full-graph probabilities are invalidated
        (exactly once) as part of the swap."""
        validate_params_like(self.params, params)
        self.set_params(params)

    # -- introspection / serialization ------------------------------------

    def producer_orders(self) -> list[tuple[str, int]]:
        """``(order, grid walks)`` of each layer of the forward
        (:func:`repro.runtime.forward.producer_orders`); empty where the
        architecture's order is fixed."""
        return _fwd.producer_orders(self.spec, self.plan.layers)

    def summary(self) -> str:
        n_params = sum(int(np.prod(np.shape(x)))
                       for x in jax.tree_util.tree_leaves(self.params))
        head = (f"Executable[{self.spec.arch}] backend={self.backend.name} "
                f"plan={self.plan_source} params={n_params} "
                f"grid={self.gt.S}x{self.gt.S} n={self.gt.n}")
        lines = [head]
        r = self.tune_report
        if r is not None:
            if r.get("winner_ms") is not None:
                vs = (f"vs analytic {r['analytic_ms']:.3f} ms "
                      f"({r['speedup']:.2f}x, " if r.get("analytic_ms")
                      else "(analytic unmeasured, ")
                lines.append(
                    f"  autotune: winner {r['winner_ms']:.3f} ms "
                    f"{vs}{r['candidates_measured']} candidates, "
                    f"{r['candidates_failed']} failed, "
                    f"{r.get('candidates_pruned', 0)} pruned)")
            else:
                lines.append(
                    f"  autotune: analytic fallback "
                    f"({r['candidates_measured']} candidates, "
                    f"{r['candidates_failed']} failed, "
                    f"{r.get('candidates_pruned', 0)} pruned)")
        lines.append(self.plan.summary())
        orders = self.producer_orders()
        if orders:
            layers = ", ".join(
                f"L{i} {o} {w} walk{'s' if w != 1 else ''}"
                for i, (o, w) in enumerate(orders))
            lines.append(f"  producer order: {layers}; "
                         f"{sum(w for _, w in orders)} grid walks per "
                         f"forward")
        return "\n".join(lines)

    def plan_json(self) -> dict:
        return self.plan.to_json()

    def save_plan(self, path) -> None:
        pathlib.Path(path).write_text(
            json.dumps(self.plan_json(), indent=2) + "\n")

    def save_params(self, path) -> None:
        np.savez(path, **_flatten_params(self.params))

    def load_params(self, path) -> dict:
        with np.load(path) as z:
            params = _unflatten_params(dict(z))
        self.set_params(params)
        return params
