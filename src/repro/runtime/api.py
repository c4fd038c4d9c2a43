"""`runtime.compile(spec, graph) -> Executable` — the one public entry.

The compile step is where the GNNerator Controller's planning lives: the
Table-I cost model picks (B, n, S, order, fused) per layer, the graph is
sharded + normalization-baked once per signature (shared via the
GraphStore), parameters are initialized (or adopted), and the forward is
jitted against one pinned kernel backend. Everything downstream — serving,
examples, benchmarks — holds an Executable instead of hand-chaining
planner/shard/init/forward.
"""
from __future__ import annotations

import functools
import hashlib

import jax
import numpy as np

from repro.core.perf_model import GNNERATOR, Platform
from repro.gnn.executor import plan_model
from repro.gnn.models import EDGE_SOFTMAX_ARCHS, ZooSpec, init_zoo
from repro.kernels import registry
from repro.runtime.cache import GraphStore, default_store
from repro.runtime.executable import Executable


def graph_fingerprint(edges: np.ndarray, num_nodes: int,
                      features: np.ndarray | None = None,
                      version: int = 0) -> str:
    """Cheap content key for an unnamed graph: shape/dtype plus a strided
    sample of the edge list AND the feature matrix (hashing all of
    reddit's ~115M edges per compile would dominate compile time).
    Features participate because the GraphStore caches the shard-grouped
    feature tensor under this key — same topology + different features
    must not collide. ``version`` (a GraphData's monotonic mutation
    counter) is folded in because the strided sample alone COLLIDES for
    graphs mutated in place: a delta that keeps the edge count and misses
    every sampled row produces the same bytes, and a pre-delta build
    would be served for the post-delta graph."""
    h = hashlib.sha1()
    edges = np.ascontiguousarray(edges)
    step = max(1, edges.shape[0] // 1024)
    h.update(str((edges.shape, str(edges.dtype), num_nodes,
                  int(version))).encode())
    h.update(edges[::step].tobytes())
    if features is not None:
        feats = np.ascontiguousarray(features)
        fstep = max(1, feats.shape[0] // 256)
        h.update(str((feats.shape, str(feats.dtype))).encode())
        h.update(feats[::fstep].tobytes())
    return h.hexdigest()


def _as_graph(graph):
    """Accept a GraphData, or (edges, num_nodes[, features])."""
    if hasattr(graph, "edges") and hasattr(graph, "profile"):
        return graph.edges, graph.profile.num_nodes, graph.features
    if isinstance(graph, (tuple, list)):
        if len(graph) == 2:
            edges, num_nodes = graph
            return np.asarray(edges), int(num_nodes), None
        edges, num_nodes, features = graph
        return np.asarray(edges), int(num_nodes), features
    raise TypeError(
        f"graph must be a GraphData or (edges, num_nodes[, features]) "
        f"tuple, got {type(graph).__name__}")


def _compile_span(fn):
    """Run ``fn`` under the ``gnn.compile`` host span, whose arguments
    count the layers of the returned Executable that run the Dense Engine
    first (``dense_first_layers``) and that run the edge softmax
    aggregation (``edge_softmax_layers``, every layer of a GAT), and name
    the device shard grid's dtype (``grid_dtype``, bfloat16 on a TPU at
    the default matmul precision) and bytes (``grid_bytes``). A model
    with edge softmax layers also counts the edge tiles' chunks each
    layer walks (``edge_softmax_chunks``) and their entries' share of the
    S²n² the dense grid holds (``edge_softmax_entry_share``, in %)."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.profiler.TraceAnnotation("gnn.compile") as span:
            exe = fn(*args, **kwargs)
            spec, gt = exe.spec, exe.gt
            attention = spec.arch in EDGE_SOFTMAX_ARCHS
            meta = dict(
                dense_first_layers=sum(
                    o == "dense-first" for o, _ in exe.producer_orders()),
                edge_softmax_layers=(len(spec.layer_dims)
                                     if attention else 0),
                grid_dtype=str(gt.blocks.dtype),
                grid_bytes=int(gt.blocks.nbytes))
            if attention:
                chunks = int(gt.tiles.count[0])
                _, rows, width = gt.tiles.mask.shape
                meta.update(
                    edge_softmax_chunks=chunks,
                    edge_softmax_entry_share=100.0 * chunks * rows * width
                    / (gt.S * gt.n) ** 2)
            span.set_metadata(**meta)
            return exe
    return wrapper


@_compile_span
def compile(spec: ZooSpec, graph, *,
            platform: Platform = GNNERATOR,
            backend: str | registry.KernelBackend | None = None,
            params: dict | None = None,
            seed: int = 0,
            max_shard_n: int = 1024,
            block_candidates: tuple[int, ...] | None = None,
            store: GraphStore | None = None,
            graph_key=None,
            graph_version: int | None = None,
            mutable_graph: bool = False,
            edge_slack: float = 0.25,
            mesh=None,
            partition: str = "contiguous",
            hub_cache: int = 256,
            donate_features: bool = False,
            plan: str = "analytic",
            tune_budget: int = 16,
            tune_seed: int = 0,
            tune_reps: int = 3,
            tune_warmup: int = 1,
            tune_timeout_s: float | None = 30.0,
            plan_cache_dir=None,
            analyze: str | None = None) -> Executable:
    """Plan, shard, initialize and jit one zoo model for one graph.

    Args:
      spec: the :class:`~repro.gnn.models.ZooSpec` to compile.
      graph: a :class:`~repro.graphs.datasets.GraphData` or an
        ``(edges, num_nodes[, features])`` tuple.
      platform: the performance-model platform the planner optimizes for.
      mesh: a ``(data, model)`` jax mesh (``launch.mesh.make_mesh_for``);
        when given the returned Executable is a
        :class:`repro.dist.gnn.ShardedExecutable` whose forward runs
        under ``shard_map`` — data axis = dst row groups placed by
        ``partition``, model axis = feature blocks.
      partition: data-axis placement method on a mesh —
        ``"contiguous"`` (historical contiguous dst-shard row ranges) or
        ``"fennel"`` (locality/degree-aware vertex placement + replicated
        hub-feature cache; see ``graphs/partition.py``). Ignored without
        ``mesh``.
      hub_cache: with ``partition="fennel"``, replicate the top-k
        out-degree vertices' features to every data group per layer
        instead of shipping them in the halo all-gather (0 disables).
        Ignored without ``mesh``.
      backend: kernel backend name/object; None resolves from the
        ``REPRO_KERNEL_BACKEND`` env var (default ``pallas``) and is then
        *pinned* into the Executable.
      params: adopt an existing param pytree; None initializes from seed.
      max_shard_n: planner cap on nodes per shard.
      store: GraphStore for the signature-keyed GraphTensors build
        (default: the module-wide store, so repeat compiles share builds).
      graph_key: cache key naming the graph contents (default: a
        fingerprint of the edge list).
      graph_version: monotonic mutation generation of the graph; None
        reads ``graph.version`` (0 for frozen graphs). Folded into the
        GraphStore key AND the default fingerprint so a mutated-in-place
        graph can never hit a pre-delta build.
      mutable_graph: build the GraphTensors through a
        :class:`repro.graphs.patch.PatchState` with ``edge_slack`` slack
        capacity, so streaming deltas (``GraphStore.patch`` /
        ``Executable.update_graph``) stay within the compiled template.
      donate_features: jit the features-passed forward path with the input
        buffer donated.
      plan: plan source — ``"analytic"`` trusts the Table-I cost model;
        ``"autotune"`` measures the analytic top-k candidates on the
        resolved backend (:func:`repro.tune.autotune_plan`) and compiles
        the measured winner, memoized through the plan cache under an
        environment-scoped key.
      tune_budget / tune_seed / tune_reps / tune_warmup / tune_timeout_s:
        autotuner knobs (max candidates measured; memo-key seed;
        median-of-k reps; warm-up runs; per-candidate timeout). Ignored
        for ``plan="analytic"``.
      plan_cache_dir: persist/load plans (and autotuned winners) as JSON
        (default: env ``REPRO_PLAN_CACHE``).
      analyze: run the compile-time static-analysis passes
        (:func:`repro.analyze.analyze_executable` — retrace, dtype, plan
        legality, comm contract on a mesh) over the compiled result.
        ``None``/``"off"`` skips; ``"warn"`` attaches the report as
        ``exe.analysis`` and emits a ``UserWarning`` for warning-or-worse
        findings; ``"error"`` additionally raises
        :class:`repro.analyze.AnalysisError` on any error finding.
    """
    if plan not in ("analytic", "autotune"):
        raise ValueError(f"plan must be 'analytic' or 'autotune', "
                         f"got {plan!r}")
    if analyze not in (None, "off", "warn", "error"):
        raise ValueError(f"analyze must be None, 'off', 'warn' or "
                         f"'error', got {analyze!r}")
    edges, num_nodes, features = _as_graph(graph)
    be = registry.resolve(backend)

    if graph_version is None:
        graph_version = int(getattr(graph, "version", 0))
    if graph_key is None:
        graph_key = graph_fingerprint(edges, num_nodes, features,
                                      version=graph_version)
    # explicit None check: GraphStore has __len__, so an empty store is falsy
    the_store = default_store() if store is None else store

    if params is None:
        params = init_zoo(jax.random.key(seed), spec)

    if partition not in ("contiguous", "fennel"):
        raise ValueError(f"partition must be 'contiguous' or 'fennel', "
                         f"got {partition!r}")

    plan_kwargs = dict(platform=platform, max_n=max_shard_n,
                       cache_dir=plan_cache_dir)
    if block_candidates is not None:
        plan_kwargs["block_candidates"] = tuple(block_candidates)
    if mesh is not None:
        # mesh compiles re-key the plan cache on the partition method:
        # the per-layer plan drives the sharded program's exchange
        # structure, so a contiguous-keyed entry must never be served for
        # a fennel compile (and vice versa)
        plan_kwargs["scope"] = {
            "mesh_partition": partition,
            "hub_cache": int(hub_cache) if partition == "fennel" else 0}

    plan_source, tune_report = "analytic", None
    if plan == "autotune" and mesh is not None:
        raise ValueError(
            "plan='autotune' measures the single-device forward and "
            "cannot tune sharded (mesh=) execution yet; compile with "
            "plan='analytic' on a mesh")
    with jax.profiler.TraceAnnotation("gnn.compile.plan"):
        if plan == "autotune":
            from repro import tune
            rec = tune.autotune_plan(
                spec, edges, num_nodes, backend=be, features=features,
                params=params, budget=tune_budget, seed=tune_seed,
                reps=tune_reps, warmup=tune_warmup,
                timeout_s=tune_timeout_s, cache_dir=plan_cache_dir,
                store=the_store, graph_key=graph_key,
                **{k: v for k, v in plan_kwargs.items()
                   if k != "cache_dir"})
            mplan, plan_source, tune_report = rec.plan, rec.plan_source, \
                rec.report()
        else:
            mplan = plan_model(spec, num_nodes, int(edges.shape[0]),
                               **plan_kwargs)

    entry = the_store.get(graph_key, edges, num_nodes, mplan.shard_n,
                          spec.arch, features=features,
                          version=graph_version, mutable=mutable_graph,
                          edge_slack=edge_slack)

    kw = dict(spec=spec, plan=mplan, backend=be, gt=entry.gt,
              h_grouped=entry.h_grouped, params=params,
              graph_key=graph_key, donate_features=donate_features,
              plan_source=plan_source, tune_report=tune_report)
    if mesh is not None:
        from repro.dist.gnn import ShardedExecutable
        # mutable graphs get capacity headroom on the fennel hub/halo
        # send slots (same slack knob as the edge-list template), so
        # streaming deltas re-partition without breaking the compiled
        # template; frozen graphs compile the tightest wire volume
        exe: Executable = ShardedExecutable(
            mesh=mesh, partition=partition, hub_cache=hub_cache,
            partition_slack=edge_slack if mutable_graph else 0.0, **kw)
    else:
        exe = Executable(**kw)
    exe.graph_version = graph_version

    if analyze in ("warn", "error"):
        from repro import analyze as _analyze
        report = _analyze.analyze_executable(exe)
        exe.analysis = report
        if analyze == "error" and report.failed("error"):
            raise _analyze.AnalysisError(report)
        if report.at_least("warning"):
            import warnings
            warnings.warn(f"static analysis of the compiled "
                          f"{spec.arch} executable:\n{report.render()}",
                          stacklevel=2)
    return exe
