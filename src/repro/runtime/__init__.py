"""repro.runtime — the compile-style GNN execution API.

    from repro import runtime
    exe = runtime.compile(spec, graph, backend="reference")
    logits = exe.forward()                  # full graph
    classes, probs = exe.predict([0, 7, 9]) # node batch, cached softmax
    print(exe.summary())

One ``compile()`` call plans, shards, initializes and jits a model.
Kernel backends (``pallas`` / ``jax`` / ``reference``) are pluggable per
compile via :mod:`repro.kernels.registry`. Plans come from one of two
sources: the analytic Table-I cost model (``plan="analytic"``, default)
or the empirical autotuner (``plan="autotune"``, :mod:`repro.tune`) that
measures the analytic top-k on the real backend and memoizes the winner.
"""
from repro.gnn.executor import clear_plan_cache, plan_cache_stats
from repro.kernels.registry import (KernelBackend, get_backend,
                                    list_backends, register_backend)
from repro.runtime.api import compile, graph_fingerprint
from repro.runtime.cache import GraphStore, default_store
from repro.runtime.executable import Executable
from repro.runtime.fit import FitResult, TrainableExecutable, fit
from repro.runtime.forward import forward
from repro.tune import clear_tune_cache, tune_cache_stats

__all__ = [
    "compile", "fit", "Executable", "TrainableExecutable", "FitResult",
    "forward", "GraphStore", "default_store",
    "KernelBackend", "get_backend", "list_backends", "register_backend",
    "plan_cache_stats", "clear_plan_cache", "graph_fingerprint",
    "tune_cache_stats", "clear_tune_cache",
]
