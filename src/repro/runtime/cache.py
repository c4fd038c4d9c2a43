"""Signature-keyed GraphTensors store (GNNIE-style graph-specific caching).

The expensive compile-time artifact is the sharded, normalization-baked
:class:`~repro.core.engines.GraphTensors` (+ shard-grouped features). One
store entry is keyed on ``(graph_key, version, normalize, self_loops,
shard_n)`` — the signature :func:`repro.gnn.models.graph_signature`
assigns each architecture, plus the graph's **monotonic version**: a
streaming delta bumps the version, so a stale build can never be returned
for a post-delta request (the key simply no longer exists). Entries are
LRU-evicted at a configurable capacity.

Mutable entries (``get(..., mutable=True)``) are built through
:class:`repro.graphs.patch.PatchState` with slack-slot edge capacity;
:meth:`GraphStore.patch` then advances them in place — incremental shard
rewrite + re-key to the new version — instead of a from-scratch rebuild.

``runtime.compile`` uses a module-default store; the serving engine owns a
private one so its capacity and stats are isolated per engine instance.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.engines import GraphTensors
from repro.gnn.models import graph_signature
from repro.kernels import registry


@dataclasses.dataclass
class GraphEntry:
    gt: GraphTensors
    h_grouped: jax.Array | None     # (S, n, F) shard-grouped features
    built_ms: float
    version: int = 0
    # numpy master copy for incremental patching; None = immutable build
    patch_state: object | None = None


class GraphStore:
    """LRU cache of sharded graph builds, keyed by normalization signature."""

    def __init__(self, max_entries: int = 8):
        # the store is shared across threads: the Server's step thread
        # reaches it through engine.mutate -> patch(), caller threads
        # through compile -> get(), and the stream trainer through its
        # own get() — one reentrant lock serializes every entry/stats
        # mutation. Lazy import: repro.runtime loads at interpreter
        # startup paths where pulling the analyze package in at module
        # scope would be a needless import-order constraint.
        from repro.analyze.lock_sanitizer import new_rlock
        self._lock = new_rlock("GraphStore._lock")
        self._entries: OrderedDict[tuple, GraphEntry] = OrderedDict()  # guarded-by: _lock
        self.max_entries = max_entries
        # built_ms_total makes rebuild churn visible: entries evicted under
        # use are rebuilt on the next miss, and only this counter shows it
        self.stats = {"hits": 0, "misses": 0, "evictions": 0,  # guarded-by: _lock
                      "built_ms_total": 0.0, "patches": 0,
                      "patch_rebuilds": 0, "patch_drops": 0,
                      "patch_ms_total": 0.0}

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def get(self, graph_key, edges: np.ndarray, num_nodes: int,
            shard_n: int, arch: str,
            features: np.ndarray | None = None, *,
            version: int = 0, mutable: bool = False,
            edge_slack: float = 0.25) -> GraphEntry:
        """Fetch-or-build the GraphTensors for ``arch``'s signature.

        ``graph_key`` identifies the graph *contents* (the serving engine
        uses its registered name; standalone compiles use a fingerprint)
        and ``version`` its mutation generation — both are part of the
        cache key, so a mutated graph can never hit a pre-delta build.
        Features are grouped once and cached alongside; an entry built
        featureless is upgraded in place on the first featureful request.
        ``mutable=True`` builds through a PatchState with ``edge_slack``
        slack capacity so later :meth:`patch` calls stay in-template.
        """
        from repro.core.sharding import shard_graph

        norm, loops = graph_signature(arch)
        key = (graph_key, version, norm, loops, shard_n)
        # fetch-or-build is atomic under the store lock: two threads
        # racing on the same signature must not both pay the sharded
        # build (and the loser must not clobber the winner's entry)
        with self._lock:
            entry = self._entries.get(key)
            # a build whose grid is stored in another dtype than the one
            # in effect here (asked for under another matmul precision)
            # is built again: a bfloat16 grid must not reach a float32 dot
            if entry is not None and \
                    not (mutable and entry.patch_state is None) and \
                    entry.gt.blocks.dtype == registry.device_grid_dtype():
                self.stats["hits"] += 1
                self._entries.move_to_end(key)
                if entry.h_grouped is None and features is not None:
                    entry.h_grouped = entry.gt.group(jnp.asarray(features))
                return entry
            self.stats["misses"] += 1
            t0 = time.perf_counter()
            with TraceAnnotation("gnn.compile.shard"):
                if mutable:
                    from repro.graphs.patch import PatchState
                    ps = PatchState(edges, num_nodes, shard_n,
                                    normalize=norm, add_self_loops=loops,
                                    slack=edge_slack)
                else:
                    ps = None
                    sg = shard_graph(edges, num_nodes, shard_n,
                                     normalize=norm, add_self_loops=loops)
            with TraceAnnotation("gnn.compile.upload"):
                gt = ps.to_graph_tensors() if mutable \
                    else GraphTensors.from_sharded(sg)
                h = gt.group(jnp.asarray(features)) \
                    if features is not None else None
            entry = GraphEntry(gt=gt, h_grouped=h,
                               built_ms=(time.perf_counter() - t0) * 1e3,
                               version=version, patch_state=ps)
            self.stats["built_ms_total"] += entry.built_ms
            self._entries[key] = entry
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self.stats["evictions"] += 1
            return entry

    @functools.partial(jax.profiler.annotate_function, name="graph.patch")
    def patch(self, graph_key, delta, *, old_version: int,
              new_version: int, features: np.ndarray | None = None) -> dict:
        """Advance every ``(graph_key, old_version)`` entry through one
        :class:`~repro.graphs.delta.GraphDelta` and re-key it to
        ``new_version``.

        Mutable entries are patched incrementally (targeted device
        updates when the template held, full transfer after a
        compaction); immutable entries are DROPPED — their consumers
        rebuild on the next versioned ``get``, which can no longer hit
        the stale build. Returns ``{(norm, loops, shard_n): (entry,
        PatchResult)}`` for the survivors. The delta is validated against
        the canonical edge list before any entry is touched, so a raising
        delta leaves the store consistent.
        """
        from repro.graphs.delta import apply_to_edge_list

        # one atomic advance: a concurrent get() sees either the
        # pre-delta entries (old key) or the re-keyed post-delta ones,
        # never a half-patched store
        with self._lock:
            keys = [k for k in self._entries
                    if k[0] == graph_key and k[1] == old_version]
            mutable_keys = [k for k in keys
                            if self._entries[k].patch_state is not None]
            if mutable_keys:
                ps0 = self._entries[mutable_keys[0]].patch_state
                # pure validation pass (raises without mutating anything)
                apply_to_edge_list(ps0.edges, ps0.num_nodes, delta)
            out = {}
            t0 = time.perf_counter()
            for k in keys:
                entry = self._entries.pop(k)
                if entry.patch_state is None:
                    self.stats["patch_drops"] += 1
                    continue
                ps = entry.patch_state
                res = ps.apply(delta)
                prev = None if res.rebuilt else entry.gt
                with TraceAnnotation("graph.upload"):
                    entry.gt = ps.to_graph_tensors(prev=prev,
                                                   pairs=res.pairs)
                    if features is not None and \
                            entry.h_grouped is not None:
                        entry.h_grouped = entry.gt.group(
                            jnp.asarray(features))
                entry.version = new_version
                self._entries[(graph_key, new_version) + k[2:]] = entry
                self.stats["patches"] += 1
                if res.rebuilt:
                    self.stats["patch_rebuilds"] += 1
                out[k[2:]] = (entry, res)
            self.stats["patch_ms_total"] += (time.perf_counter() - t0) * 1e3
            return out

    def evict(self, graph_key=None) -> None:
        """Drop entries for one graph_key, or everything when None."""
        with self._lock:
            if graph_key is None:
                self._entries.clear()
                return
            for key in [k for k in self._entries if k[0] == graph_key]:
                del self._entries[key]


# module-default store shared by standalone runtime.compile() calls
_DEFAULT_STORE = GraphStore()


def default_store() -> GraphStore:
    return _DEFAULT_STORE
