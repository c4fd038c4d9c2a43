"""Retrace + dtype-drift passes over jitted entry points and their jaxprs.

**Retrace pass.** A production jit entry point must trace once and serve
forever; every extra trace is seconds of XLA compile charged to some
unlucky request. The static halves of the pass flag the *causes*
(Python-scalar pytree leaves → weak-typed tracers that retrace when a
typed value arrives; ``jax.jit`` built inside a loop — see
``ast_lint.RT101``); the dynamic half (:func:`trace_stability`) is the
*oracle*: drive the entry point with a representative call sequence and
read the jit cache size — anything above the expected trace count is a
finding, whatever the cause.

**Dtype pass.** Walks a jaxpr (sub-jaxprs included) for

  * f64/c128 values — unintended x64 promotion doubles every buffer and
    silently halves throughput on accelerators,
  * weak-typed entry arguments — the Python-scalar signature that both
    promotes dtypes *and* retraces when a typed array arrives,
  * arrays beyond int32 element count — at reddit-scale node counts a
    flattened int32 index (edge gathers, dense shard grids) wraps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.analyze.report import Finding

_INT32_MAX = 2 ** 31 - 1


# --------------------------------------------------------------------------
# retrace
# --------------------------------------------------------------------------

def cache_size(fn) -> int | None:
    """Size of a jitted callable's trace cache; None when ``fn`` does not
    expose one (not a jit wrapper)."""
    probe = getattr(fn, "_cache_size", None)
    if probe is None:
        return None
    try:
        return int(probe())
    except Exception:   # pragma: no cover - defensive
        return None


def python_scalar_leaves(tree, *, name: str,
                         pass_name: str = "retrace") -> list[Finding]:
    """RT002: Python int/float/bool leaves in an argument pytree trace as
    weak-typed values — the jit signature changes (and retraces) the
    moment a caller passes a typed array instead, and the weak dtype can
    promote everything it touches."""
    out: list[Finding] = []
    leaves, _ = jax.tree_util.tree_flatten(tree)
    for i, leaf in enumerate(leaves):
        if isinstance(leaf, (bool, int, float)) and \
                not isinstance(leaf, np.generic):
            out.append(Finding(
                rule="RT002", severity="warning", pass_name=pass_name,
                message=f"pytree leaf {i} is a Python "
                        f"{type(leaf).__name__} ({leaf!r}); it traces "
                        f"weak-typed and retraces when a typed array "
                        f"arrives — wrap it in jnp.asarray with an "
                        f"explicit dtype",
                location=name))
    return out


def trace_stability(fn, calls, *, name: str,
                    max_traces: int = 1) -> list[Finding]:
    """RT003: drive a jitted ``fn`` with every args-tuple in ``calls``
    and flag cache growth beyond ``max_traces`` — the dynamic retrace
    oracle (shape-dependent rebinds, scalar closures, donation misses all
    surface here regardless of cause)."""
    before = cache_size(fn)
    if before is None:
        return [Finding(
            rule="RT000", severity="info", pass_name="retrace",
            message="entry point exposes no jit trace cache; retrace "
                    "probe skipped", location=name)]
    for args in calls:
        jax.block_until_ready(fn(*args))
    after = cache_size(fn)
    if after is not None and after > max_traces:
        return [Finding(
            rule="RT003", severity="error", pass_name="retrace",
            message=f"{len(calls)} same-spec calls produced {after} "
                    f"traces (expected <= {max_traces}); a per-request "
                    f"recompile is hiding in this entry point",
            location=name)]
    return []


# --------------------------------------------------------------------------
# dtype drift
# --------------------------------------------------------------------------

def _iter_sub_jaxprs(params: dict):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for item in vs:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def _walk_eqns(jaxpr, visit) -> None:
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in _iter_sub_jaxprs(eqn.params):
            _walk_eqns(sub, visit)


def _aval_of(var):
    return getattr(var, "aval", None)


def dtype_findings(closed_jaxpr, *, name: str,
                   allow_f64: bool = False) -> list[Finding]:
    """Walk one ClosedJaxpr for the dtype-drift rules (see module
    docstring): DT001 f64/c128 values, DT002 weak-typed entry arguments,
    DT003 arrays past int32 element count."""
    out: list[Finding] = []
    jaxpr = closed_jaxpr.jaxpr

    for i, var in enumerate(jaxpr.invars):
        aval = _aval_of(var)
        if aval is None or not hasattr(aval, "dtype"):
            continue
        if getattr(aval, "weak_type", False):
            out.append(Finding(
                rule="DT002", severity="warning", pass_name="dtype",
                message=f"entry argument {i} is weak-typed "
                        f"({aval.dtype}); it came from a Python scalar "
                        f"and will both promote dtypes and retrace when "
                        f"a typed array is passed",
                location=name))

    seen_f64: set[str] = set()
    seen_big: set[str] = set()

    def visit(eqn):
        prim = eqn.primitive.name
        for var in (*eqn.invars, *eqn.outvars):
            aval = _aval_of(var)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            dt = np.dtype(aval.dtype)
            if not allow_f64 and dt in (np.dtype(np.float64),
                                        np.dtype(np.complex128)) \
                    and prim not in seen_f64:
                seen_f64.add(prim)
                out.append(Finding(
                    rule="DT001", severity="error", pass_name="dtype",
                    message=f"{dt} value flows through '{prim}' — "
                            f"unintended x64 promotion doubles every "
                            f"buffer it touches; pin the input dtype or "
                            f"cast at the boundary",
                    location=name))
            shape = getattr(aval, "shape", ())
            if shape and int(np.prod(shape, dtype=np.int64)) > _INT32_MAX \
                    and prim not in seen_big:
                seen_big.add(prim)
                out.append(Finding(
                    rule="DT003", severity="warning", pass_name="dtype",
                    message=f"'{prim}' touches an array of "
                            f"{int(np.prod(shape, dtype=np.int64)):,} "
                            f"elements (> int32 max); flattened int32 "
                            f"indexing (edge gathers, dense shard grids) "
                            f"wraps at this scale — use int64 indices or "
                            f"shard the tensor",
                    location=name))

    _walk_eqns(jaxpr, visit)
    return out


# --------------------------------------------------------------------------
# Executable-level entry
# --------------------------------------------------------------------------

def _forward_avals(exe):
    """(params-avals, h-aval) matching one compiled Executable."""
    p_avals = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x)),
        exe.params)
    h = exe._h_grouped
    if h is not None:
        h_aval = jax.ShapeDtypeStruct(jnp.shape(h), jnp.result_type(h))
    else:
        h_aval = jax.ShapeDtypeStruct(
            (exe.gt.S, exe.gt.n, exe.spec.in_dim), jnp.float32)
    return p_avals, h_aval


def check_executable(exe, *, probe: bool = False,
                     batch_sizes=(1, 2, 3, 5, 7),
                     graph=None) -> list[Finding]:
    """Static (always) + dynamic (``probe=True``) analysis of one
    compiled :class:`~repro.runtime.executable.Executable`:

      * RT002 over the parameter pytree (scalar leaves),
      * DT001/2/3 over the traced forward jaxpr (abstract avals — no
        device work, no memory for the activations),
      * with ``probe``: RT003 trace-stability of the jitted forward
        (repeat full-graph calls must not add traces) and of the
        node-batch gather (varying batch sizes within one pad bucket
        must share one trace),
      * with ``probe`` and a ``graph`` (the GraphData the Executable was
        compiled from): RT003 over the MUTATION path — an in-template
        :class:`~repro.graphs.delta.GraphDelta` pushed through
        ``update_graph`` must not add traces
        (:func:`check_mutation_stability`).
    """
    name = f"Executable[{exe.spec.arch}]"
    out = python_scalar_leaves(exe.params, name=f"{name}.params")

    p_avals, h_aval = _forward_avals(exe)
    closed = jax.make_jaxpr(exe._forward_fn())(p_avals, h_aval)
    out.extend(dtype_findings(closed, name=f"{name}.forward"))

    if probe and exe._h_grouped is not None:
        out.extend(trace_stability(
            exe._jit_forward,
            [(exe.params, exe._h_grouped, *exe._graph_args())] * 2,
            name=f"{name}.forward"))
        if graph is not None:
            out.extend(check_mutation_stability(
                exe, graph.edges, graph.profile.num_nodes, name=name))
        # node-batch path: distinct batch sizes inside one pad bucket
        # must not add gather traces (the PR-7 serving retrace fix)
        n = exe.gt.num_nodes
        for k in batch_sizes:
            exe.forward_nodes(np.arange(min(k, n)))
        gather_traces = cache_size(exe._jit_gather)
        buckets = len({exe._gather_bucket(min(k, n))
                       for k in batch_sizes})
        if gather_traces is not None and gather_traces > buckets:
            out.append(Finding(
                rule="RT003", severity="error", pass_name="retrace",
                message=f"node-batch gather traced {gather_traces}x for "
                        f"{buckets} pad bucket(s) — per-batch-shape "
                        f"recompiles are back",
                location=f"{name}.forward_nodes"))
    return out


def check_mutation_stability(exe, edges, num_nodes, deltas=None, *,
                             name: str | None = None) -> list[Finding]:
    """RT003 on the mutation path: push ``deltas`` through the
    incremental patcher (:class:`~repro.graphs.patch.PatchState` sized to
    the Executable's exact edge-list template) and
    ``Executable.update_graph``, then read the forward's jit cache —
    streaming serving depends on in-template edge churn being
    recompile-free, so any added trace is the finding.

    A delta that BREAKS the template is the same finding from the other
    side: the patcher compacted (edge-capacity overflow / node growth
    past S·n) and ``update_graph`` refused the new shapes — the
    unpadded/slack=0 known-bad fixture lands here.

    With ``deltas=None`` the probe deletes-then-reinserts one existing
    edge: guaranteed in-capacity, and it leaves the Executable's graph
    state bitwise where it started. Caller-supplied deltas are applied
    for real (the probe is meant for throwaway analysis compiles).
    """
    from repro.gnn.models import graph_signature
    from repro.graphs.delta import GraphDelta
    from repro.graphs.patch import PatchState

    name = name or f"Executable[{exe.spec.arch}]"
    loc = f"{name}.update_graph"
    if cache_size(exe._jit_forward) is None:
        return [Finding(
            rule="RT000", severity="info", pass_name="retrace",
            message="forward exposes no jit trace cache; mutation "
                    "retrace probe skipped", location=loc)]
    # warm: the pre-mutation trace is legitimate — only growth counts
    jax.block_until_ready(exe.forward())
    before = cache_size(exe._jit_forward)

    edges = np.asarray(edges, dtype=np.int64)
    if deltas is None:
        if edges.shape[0] == 0:
            return []
        e = edges[:1]
        deltas = [GraphDelta(del_edges=e), GraphDelta(add_edges=e)]

    norm, loops = graph_signature(exe.spec.arch)
    ps = PatchState(edges, num_nodes, exe.gt.n, normalize=norm,
                    add_self_loops=loops,
                    edge_capacity=int(exe.gt.edge_src.shape[-1]))
    out: list[Finding] = []
    for d in deltas:
        res = ps.apply(d)
        try:
            exe.update_graph(ps.to_graph_tensors(), exe._h_grouped)
        except ValueError as err:
            out.append(Finding(
                rule="RT003", severity="error", pass_name="retrace",
                message=f"delta {d.summary()} broke the graph template "
                        f"({err}; patcher "
                        f"{'compacted' if res.rebuilt else 'patched'}) — "
                        f"this mutation forces a recompile mid-serve; "
                        f"build with edge slack "
                        f"(compile(mutable_graph=True)) or shrink the "
                        f"delta",
                location=loc))
            return out
        jax.block_until_ready(exe.forward())
    after = cache_size(exe._jit_forward)
    if after is not None and after > before:
        out.append(Finding(
            rule="RT003", severity="error", pass_name="retrace",
            message=f"{len(deltas)} in-template delta(s) grew the "
                    f"forward trace cache {before} -> {after}; graph "
                    f"mutation is recompiling the forward (graph tensors "
                    f"must enter the jit as arguments, not closures)",
            location=loc))
    return out
