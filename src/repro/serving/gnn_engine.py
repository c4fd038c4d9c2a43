"""GNN node-classification engine: the compile/cache core under the Server.

Requests name a registered graph + model and a set of node ids. The engine
implements the serving :class:`~repro.serving.api.Engine` step protocol —
``route`` validates a request and streams it by (model, graph), ``step``
answers one formed micro-batch from a compiled
:class:`repro.runtime.Executable`, cached per (model, graph) — so the
continuous-batching :class:`~repro.serving.api.Server` can drive it
interchangeably with the LM engine. The two serving caches are both
runtime-owned:

  * **graph-tensor cache** — the engine owns a private
    :class:`repro.runtime.GraphStore`; ``runtime.compile`` pulls each
    Executable's sharded, normalization-baked ``GraphTensors`` (+
    shard-grouped features) from it, keyed on ``(graph, normalize,
    self_loops, shard_n)`` — the signature
    :func:`repro.gnn.models.graph_signature` assigns each architecture —
    so every model needing the same signature shares one entry.
    LRU-evicted at a configurable capacity.
  * **logits cache** — full-graph inference is the natural unit on an
    accelerator (one shard-grid sweep per layer covers every node), so
    each Executable computes class probabilities for ALL nodes once
    (:meth:`Executable.full_probs`); every later node id on that pair is
    a pure gather. Invalidate with :meth:`GNNServeEngine.invalidate`
    after a weight swap.

Latency accounting is per request: ``Prediction.engine_ms`` is the time
spent answering THAT request (the cold full-graph forward is charged to
the request that triggered it, later requests pay only their gather);
compile time is never folded into request latency — it accrues to
``stats["compile_ms_total"]``. ``queue_ms`` is stamped by the Server.

``serve()`` is the synchronous batch core; the Server path answers each
micro-batch through the same ``route``/``step``.

Layer execution plans come from the content-hash-memoized planner inside
``runtime.compile`` — block size B, traversal order and fused/two-stage
per layer from the Table-I cost model, shard size from the on-chip budget.

Passing ``mesh=`` (a ``(data, model)`` jax mesh from
``launch.mesh.make_mesh_for``) makes every compiled unit a sharded
:class:`repro.dist.gnn.ShardedExecutable`: same serving protocol, forward
computed across the mesh (``launch/serve.py --mesh N`` wires this up).
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict
from typing import Sequence

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from repro import runtime
from repro.gnn.executor import ModelPlan
from repro.gnn.models import ZooSpec, init_zoo
from repro.graphs.datasets import GraphData


@dataclasses.dataclass
class NodeRequest:
    """Classify ``node_ids`` of ``graph`` with ``model``."""

    graph: str
    node_ids: np.ndarray            # (k,) int
    model: str = "gcn"


@dataclasses.dataclass
class Prediction:
    graph: str
    model: str
    node_ids: np.ndarray
    classes: np.ndarray             # (k,) int32 argmax class per node
    probs: np.ndarray               # (k,) float32 softmax mass of the argmax
    queue_ms: float = 0.0           # admission -> dispatch (Server-stamped)
    engine_ms: float = 0.0          # THIS request's engine time
    latency_ms: float = 0.0         # queue_ms + engine_ms (back-compat)


@dataclasses.dataclass
class _ModelEntry:
    spec: ZooSpec
    params: dict


class GNNServeEngine:
    """Batched node-classification inference over named graphs/models."""

    def __init__(self, *, max_graph_entries: int = 8,
                 max_shard_n: int = 1024, max_dense_gib: float = 8.0,
                 backend: str | None = None, mesh=None,
                 partition: str = "contiguous", hub_cache: int = 256,
                 plan: str = "analytic", tune_budget: int = 16,
                 streaming: bool = False, edge_slack: float = 0.25,
                 invalidation: str = "targeted"):
        if plan not in ("analytic", "autotune"):
            raise ValueError(f"plan must be 'analytic' or 'autotune', "
                             f"got {plan!r}")
        if partition not in ("contiguous", "fennel"):
            raise ValueError(f"partition must be 'contiguous' or 'fennel', "
                             f"got {partition!r}")
        if invalidation not in ("targeted", "full"):
            raise ValueError(f"invalidation must be 'targeted' or 'full', "
                             f"got {invalidation!r}")
        if plan == "autotune" and mesh is not None:
            raise ValueError("plan='autotune' cannot tune sharded (mesh=) "
                             "execution; use plan='analytic' with mesh")
        # registries + compiled units: mutated only by register_* /
        # reload_params / mutate, all of which the Server serializes with
        # engine steps (Server._step_lock); read by route() under
        # Server._cv. Documented-invariant attrs, verified by the
        # concurrency pass (`repro.analyze.concurrency`).
        self._graphs: dict[str, GraphData] = {}       # guarded-by: Server._step_lock
        self._models: dict[str, _ModelEntry] = {}     # guarded-by: Server._step_lock
        self._store = runtime.GraphStore(max_entries=max_graph_entries)
        # plan source every compiled unit uses: "analytic" (Table-I cost
        # model) or "autotune" (measured winners, repro.tune) — the first
        # request on a (model, graph) pair pays the tuning run, later
        # compiles hit the winner store
        self.plan_source = plan
        self.tune_budget = tune_budget
        # a (data, model) jax mesh: compiled units become sharded
        # Executables (repro.dist.gnn) serving from every device;
        # `partition` picks the data-axis placement ("contiguous" row
        # ranges or the "fennel" locality partitioner with a `hub_cache`
        # replicated hub-feature cache) — ignored without a mesh
        self.mesh = mesh
        self.partition = partition
        self.hub_cache = hub_cache
        # compiled (model, graph) units; each owns the full-graph softmax
        # that warm requests gather from
        self._executables: dict[tuple[str, str],
                                runtime.Executable] = {}  # guarded-by: Server._step_lock
        self.max_shard_n = max_shard_n
        self.max_dense_gib = max_dense_gib
        self.backend = backend
        # streaming mode: graph builds go through PatchState (slack-slot
        # edge capacity) so GraphDelta mutations stay within the compiled
        # template; "targeted" invalidation drops only the delta's k-hop
        # affected softmax rows, "full" flushes per mutate (the
        # flush-everything baseline the benchmark compares against)
        self.streaming = streaming
        self.edge_slack = edge_slack
        self.invalidation = invalidation
        self._graph_versions: dict[str, int] = {}  # guarded-by: Server._step_lock
        # per-graph accumulated delta-touched node ids, consumed by the
        # stream trainer (take_dirty) to pick fine-tune neighborhoods.
        # The ONE engine attribute with its own lock: mutate() appends
        # under Server._step_lock but the trainer's take_dirty() pops
        # from the trainer thread WITHOUT the step lock — without
        # _dirty_lock the get/union/assign in mutate races the pop and
        # loses touched-node sets.
        from repro.analyze.lock_sanitizer import new_lock
        self._dirty_lock = new_lock("GNNServeEngine._dirty_lock")
        self._dirty: dict[str, np.ndarray] = {}  # guarded-by: _dirty_lock
        self._stats = {  # guarded-by: Server._step_lock (step/reload/mutate serialized)
            "logits_cache_hits": 0, "logits_cache_misses": 0,
            "requests": 0, "batches": 0, "nodes_served": 0,
            "compiles": 0, "compile_ms_total": 0.0,
            "reloads": 0, "logits_invalidations": 0,
            "mutations": 0, "mutate_ms_total": 0.0,
            "targeted_invalidations": 0, "full_invalidations": 0,
            "nodes_invalidated": 0, "graph_recompiles": 0,
        }

    @property
    def stats(self) -> dict:
        """Serving counters merged with the runtime graph-store counters
        (kept under the historical key names)."""
        s = self._store.stats
        return {**self._stats,
                "graph_cache_hits": s["hits"],
                "graph_cache_misses": s["misses"],
                "graph_cache_evictions": s["evictions"],
                "graph_built_ms_total": s["built_ms_total"],
                "graph_patches": s["patches"],
                "graph_patch_rebuilds": s["patch_rebuilds"],
                "graph_patch_drops": s["patch_drops"],
                "graph_patch_ms_total": s["patch_ms_total"]}

    # -- registration ------------------------------------------------------

    def register_graph(self, name: str, data: GraphData) -> None:
        # fail fast before sharding: densified shard blocks cost
        # (padded N)² · 4 bytes, which for e.g. full-scale reddit is ~200 TiB
        n_pad = -(-data.profile.num_nodes // self.max_shard_n) * self.max_shard_n
        est_bytes = n_pad ** 2 * 4
        if est_bytes > self.max_dense_gib * 2 ** 30:
            raise ValueError(
                f"graph {name!r} ({data.profile.num_nodes} nodes) would "
                f"densify to ~{est_bytes / 2**30:.0f} GiB of shard blocks "
                f"(limit {self.max_dense_gib} GiB); register a scaled-down "
                f"dataset (make_dataset(..., scale=...)) or raise "
                f"max_dense_gib")
        self._graphs[name] = data
        self._graph_versions[name] = int(getattr(data, "version", 0))
        with self._dirty_lock:
            self._dirty.pop(name, None)
        # stale sharded tensors / executables for a replaced graph must go
        self._store.evict(name)
        for key in [k for k in self._executables if k[1] == name]:
            del self._executables[key]

    def register_model(self, name: str, spec: ZooSpec,
                       params: dict | None = None, *, seed: int = 0) -> None:
        if params is None:
            import jax
            params = init_zoo(jax.random.key(seed), spec)
        self._models[name] = _ModelEntry(spec=spec, params=params)
        # a (re-)registered model invalidates its compiled units wholesale:
        # the spec (and thus plan/graph signature) may have changed
        for key in [k for k in self._executables if k[0] == name]:
            del self._executables[key]

    def invalidate(self, *, model: str | None = None,
                   graph: str | None = None) -> None:
        """Drop cached logits (e.g. after a parameter update)."""
        for (m, g), exe in self._executables.items():
            if (model is None or m == model) and (graph is None or g == graph):
                exe.invalidate()

    def reload_params(self, model: str, params: dict) -> int:
        """Hot weight reload: swap ``model``'s parameters into every
        compiled Executable **without recompiling** (same shapes, same jit
        traces — :meth:`Executable.update_params` validates the tree).
        Each affected Executable's logits cache is invalidated exactly
        once, as part of the swap; later compiles on new graphs adopt the
        new weights too.

        Thread-safety is the Server's job: drive this through
        :meth:`repro.serving.Server.reload` so the swap is serialized
        with engine steps — the in-flight micro-batch finishes on the old
        weights, every later batch sees the new ones.
        """
        from repro.runtime.executable import validate_params_like

        ent = self._models[model]          # KeyError for unknown models
        # validate against the registered params BEFORE touching any
        # Executable, so a bad reload is all-or-nothing even when several
        # compiled units (or none yet) hold the model
        try:
            validate_params_like(ent.params, params)
        except ValueError as err:
            raise ValueError(
                f"reload for model {model!r} rejected: {err}") from None
        touched = 0
        for (m, _g), exe in self._executables.items():
            if m == model:
                exe.update_params(params)  # same-shape swap; invalidates once
                touched += 1
        ent.params = params
        self._stats["reloads"] += 1
        self._stats["logits_invalidations"] += touched
        return touched

    # -- streaming mutation path -------------------------------------------

    @functools.partial(jax.profiler.annotate_function, name="graph.mutate")
    def mutate(self, graph: str, delta) -> dict:
        """Apply one :class:`~repro.graphs.delta.GraphDelta` to a
        registered graph: mutate the GraphData in place (version bump),
        advance every store build through the incremental patcher, and
        push the post-delta tensors into every compiled Executable
        serving the graph — *without recompiling* while the delta stays
        within the slack-slot template.

        Invalidation is **targeted** (default): only the softmax rows in
        the delta's (num_layers-1)-out-hop affected neighborhood are
        dropped (normalization-aware seeds from
        :func:`~repro.graphs.delta.seed_nodes`); every other cached row
        keeps serving. A compaction (template break) drops the
        Executable instead — the next request recompiles it against the
        current build, counted in ``graph_recompiles``.

        Thread-safety is the Server's job: drive this through
        :meth:`repro.serving.Server.mutate` so the swap is serialized
        with engine steps — in-flight micro-batches finish on the
        pre-delta snapshot.
        """
        from repro.gnn.models import graph_signature
        from repro.graphs.delta import (affected_nodes, apply_to_graph_data,
                                        seed_nodes, touched_nodes)
        from repro.graphs.patch import pair_rows

        data = self._graphs[graph]         # KeyError for unknown graphs
        t0 = time.perf_counter()
        edges_before = np.array(data.edges, copy=True)
        num_before = data.profile.num_nodes
        with TraceAnnotation("graph.apply_delta"):
            apply_to_graph_data(data, delta)   # validates before 1st write
        old_v = self._graph_versions.get(graph, 0)
        new_v = int(data.version)
        self._graph_versions[graph] = new_v
        patched = self._store.patch(graph, delta, old_version=old_v,
                                    new_version=new_v,
                                    features=data.features
                                    if delta.add_nodes else None)

        touched = touched_nodes(delta, edges_before, num_before)
        # read-union-write must be atomic against the trainer thread's
        # take_dirty() pop; the store patch above stays OUTSIDE this
        # lock (its own lock serializes it) to keep the hold short
        with self._dirty_lock:
            prev = self._dirty.get(graph)
            self._dirty[graph] = (touched if prev is None
                                  else np.union1d(prev, touched))

        per_model = []
        with TraceAnnotation("graph.invalidate"):
            for key in [k for k in self._executables if k[1] == graph]:
                model = key[0]
                exe = self._executables[key]
                spec = self._models[model].spec
                norm, loops = graph_signature(spec.arch)
                hit = patched.get((norm, loops, exe.plan.shard_n))
                if hit is None:
                    # no surviving build for this signature (immutable entry
                    # dropped, or evicted under LRU) — recompile lazily
                    del self._executables[key]
                    self._stats["graph_recompiles"] += 1
                    per_model.append({"model": model, "recompile": True})
                    continue
                entry, res = hit
                targeted = (self.invalidation == "targeted"
                            and not res.rebuilt)
                stale = None
                if targeted:
                    ps = entry.patch_state
                    seeds = seed_nodes(delta, edges_before, ps.edges,
                                       num_before, norm)
                    stale = affected_nodes(ps.edges, seeds,
                                           len(spec.layer_dims) - 1,
                                           data.profile.num_nodes)
                try:
                    rows = (exe._probs.shape[0]
                            if exe.has_cached_probs else 0)
                    # placement re-score hint for fennel-partitioned sharded
                    # units: the patch's affected shard rows/cols (available
                    # even under invalidation="full"); plain executables
                    # ignore it
                    refine = pair_rows(res.pairs, exe.gt.n,
                                       data.profile.num_nodes)
                    n_inv = exe.update_graph(entry.gt, entry.h_grouped,
                                             stale_nodes=stale,
                                             refine_nodes=refine)
                    exe.graph_version = new_v
                except ValueError:
                    # compaction changed the template: drop + recompile lazily
                    del self._executables[key]
                    self._stats["graph_recompiles"] += 1
                    per_model.append({"model": model, "recompile": True})
                    continue
                if targeted:
                    self._stats["targeted_invalidations"] += 1
                else:
                    self._stats["full_invalidations"] += 1
                self._stats["nodes_invalidated"] += n_inv
                per_model.append({
                    "model": model, "recompile": False, "targeted": targeted,
                    "rows_invalidated": n_inv, "rows_cached": rows,
                    "affected_nodes": int(stale.size) if stale is not None
                    else data.profile.num_nodes})
        ms = (time.perf_counter() - t0) * 1e3
        self._stats["mutations"] += 1
        self._stats["mutate_ms_total"] += ms
        return {"graph": graph, "version": new_v, "ops": delta.num_ops,
                "touched_nodes": int(touched.size),
                "num_nodes": data.profile.num_nodes,
                "num_edges": data.profile.num_edges,
                "mutate_ms": ms, "executables": per_model}

    def take_dirty(self, graph: str) -> np.ndarray:
        """Pop the accumulated delta-touched node ids for ``graph`` (the
        stream trainer's fine-tune seed pool). Empty array when clean.
        Called from the trainer thread while mutations land through the
        step lock, hence the dedicated lock."""
        with self._dirty_lock:
            return self._dirty.pop(graph, np.empty(0, dtype=np.int64))

    # -- accessors (stream trainer plumbing) -------------------------------

    def graph_data(self, name: str) -> GraphData:
        return self._graphs[name]

    def graph_version(self, name: str) -> int:
        return self._graph_versions.get(name, 0)

    def model_spec(self, name: str) -> ZooSpec:
        return self._models[name].spec

    def model_params(self, name: str) -> dict:
        return self._models[name].params

    # -- compile path ------------------------------------------------------

    def executable(self, model: str, graph: str) -> runtime.Executable:
        """Fetch-or-compile the Executable serving a (model, graph) pair.

        Compile time accrues to ``stats["compile_ms_total"]`` — it is a
        per-(model, graph) setup cost, never charged to request latency.
        """
        key = (model, graph)
        exe = self._executables.get(key)
        if exe is None:
            ent = self._models[model]
            t0 = time.perf_counter()
            exe = runtime.compile(
                ent.spec, self._graphs[graph], params=ent.params,
                backend=self.backend, max_shard_n=self.max_shard_n,
                store=self._store, graph_key=graph, mesh=self.mesh,
                partition=self.partition, hub_cache=self.hub_cache,
                plan=self.plan_source, tune_budget=self.tune_budget,
                graph_version=self._graph_versions.get(graph, 0),
                mutable_graph=self.streaming, edge_slack=self.edge_slack)
            self._executables[key] = exe
            self._stats["compiles"] += 1
            self._stats["compile_ms_total"] += \
                (time.perf_counter() - t0) * 1e3
        return exe

    def model_plan(self, model: str, graph: str) -> ModelPlan:
        """The layer-execution plan a (model, graph) pair is compiled with."""
        return self.executable(model, graph).plan

    # -- Engine step protocol (what the Server drives) ---------------------

    def route(self, req: NodeRequest) -> tuple[str, str]:
        """Validate one request and name its stream: the (model, graph)
        pair, so the scheduler micro-batches work that shares an
        Executable (and its cached full-graph softmax)."""
        if req.model not in self._models:
            raise KeyError(f"unknown model {req.model!r}")
        if req.graph not in self._graphs:
            raise KeyError(f"unknown graph {req.graph!r}")
        if self.mesh is not None:
            # sharded execution covers the linear-aggregation family only;
            # reject HERE (admission -> typed Rejected on the ticket)
            # instead of letting runtime.compile raise inside step(),
            # which would Fail every co-batched request on the stream
            from repro.dist.gnn import SUPPORTED_ARCHS
            arch = self._models[req.model].spec.arch
            if arch not in SUPPORTED_ARCHS:
                raise NotImplementedError(
                    f"model {req.model!r} ({arch}) cannot run on a mesh: "
                    f"sharded execution supports {SUPPORTED_ARCHS}")
        ids = np.asarray(req.node_ids, dtype=np.int64)
        n_nodes = self._graphs[req.graph].profile.num_nodes
        if ids.size and (ids.min() < 0 or ids.max() >= n_nodes):
            raise IndexError(f"node ids out of range for graph "
                             f"{req.graph!r} ({n_nodes} nodes)")
        return (req.model, req.graph)

    def step(self, key: tuple[str, str],
             payloads: Sequence[NodeRequest]) -> list:
        """Answer one formed micro-batch (all requests share ``key``'s
        Executable). Results match ``payloads`` positionally; a request
        whose node ids went stale between admission and dispatch (graph
        re-registered smaller) yields its ValueError positionally so the
        Server fails THAT ticket alone — co-batched valid requests still
        complete."""
        model, graph = key
        exe = self.executable(model, graph)
        checked: list[np.ndarray | Exception] = []
        for r in payloads:
            try:
                checked.append(exe._check_node_ids(r.node_ids))
            except ValueError as err:
                checked.append(err)
        id_batches = [ids for ids in checked
                      if not isinstance(ids, Exception)]
        # one cache touch per VALID request (stale-id requests never reach
        # the cache): the batch's first touch may compute the full-graph
        # softmax, the rest count as hits. A mutation-staled row counts
        # as the batch's one miss too — it forces the same recompute.
        fresh = exe.has_cached_probs and all(
            exe.probs_fresh_for(ids) for ids in id_batches)
        miss = 0 if fresh or not id_batches else 1
        self._stats["logits_cache_misses"] += miss
        self._stats["logits_cache_hits"] += len(id_batches) - miss
        answers = iter(exe.step(id_batches))
        out: list = []
        for ids in checked:
            if isinstance(ids, Exception):
                out.append(ids)
                continue
            classes, probs, ms = next(answers)
            out.append(Prediction(
                graph=graph, model=model, node_ids=ids, classes=classes,
                probs=probs, engine_ms=ms, latency_ms=ms))
            self._stats["requests"] += 1
            self._stats["nodes_served"] += int(ids.size)
        self._stats["batches"] += 1
        return out

    # -- synchronous batch core --------------------------------------------

    def serve(self, requests: Sequence[NodeRequest]) -> list[Prediction]:
        """Serve a batch synchronously; answers keep the caller's request
        order. (The async path is ``repro.serving.Server.submit`` — this
        core micro-batches by (model, graph) without queueing.)"""
        # validate everything before touching caches/stats so a bad request
        # rejects the batch atomically instead of half-serving it
        groups: OrderedDict[tuple[str, str], list[int]] = OrderedDict()
        for i, r in enumerate(requests):
            groups.setdefault(self.route(r), []).append(i)

        out: list[Prediction | None] = [None] * len(requests)
        for key, idxs in groups.items():
            preds = self.step(key, [requests[j] for j in idxs])
            for i, pred in zip(idxs, preds):
                out[i] = pred
        return out  # type: ignore[return-value]

    def cache_report(self) -> str:
        s = self.stats
        g_tot = s["graph_cache_hits"] + s["graph_cache_misses"]
        l_tot = s["logits_cache_hits"] + s["logits_cache_misses"]
        return (f"graph-tensor cache: {s['graph_cache_hits']}/{g_tot} hits "
                f"({len(self._store)} resident, "
                f"{s['graph_cache_evictions']} evicted, "
                f"{s['graph_built_ms_total']:.0f} ms building) | "
                f"logits cache: {s['logits_cache_hits']}/{l_tot} hits | "
                f"{s['compiles']} executables compiled "
                f"({s['compile_ms_total']:.0f} ms) | "
                f"{s['requests']} requests, {s['nodes_served']} nodes in "
                f"{s['batches']} batches")
