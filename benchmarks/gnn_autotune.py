"""Autotuner benchmark: analytic vs measured-winner layer plans on the
Pallas backend, recorded to BENCH_gnn.json (`autotune` section).

For each Table-II graph (scaled down — off-TPU the Pallas kernels run in
interpret mode, which pays a large per-element cost), compile the gcn
zoo model twice on the pallas backend:

  * ``plan="autotune"`` — the repro.tune harness measures up to
    ``budget`` candidate plans (the analytic Table-I plan is always
    candidate #0) and picks the fastest median forward.
  * a second ``plan="autotune"`` compile — must hit the persistent
    winner store with **zero** new candidate measurements (the
    acceptance criterion for the tuner's memoization).

Each row records the measured analytic and autotuned medians, the
speedup (>= 1 by construction whenever the analytic candidate measures
ok), the winning per-layer config, and whether the second compile was a
pure cache hit.

    PYTHONPATH=src python -m benchmarks.gnn_autotune --budget 6
"""
from __future__ import annotations

import argparse
import time

from benchmarks.report import merge_bench_json

# (name, scale): calibrated so one interpret-mode forward stays well under
# a second (citeseer smallest: its 3703-dim features dominate the cost)
GRAPHS = (("cora", 0.25), ("citeseer", 0.15), ("pubmed", 0.05))
ARCH = "gcn"
BACKEND = "pallas"
BUDGET = 6
MAX_SHARD_N = 256
TIMEOUT_S = 120.0


def bench_gnn_autotune(budget: int = BUDGET, backend: str = BACKEND):
    from repro import env, runtime
    from repro.gnn.models import ZooSpec
    from repro.graphs.datasets import make_dataset

    runtime.clear_tune_cache()
    rows = []
    for name, scale in GRAPHS:
        ds = make_dataset(name, seed=0, scale=scale)
        prof = ds.profile
        spec = ZooSpec(ARCH, prof.feature_dim, 16, prof.num_classes,
                       num_layers=2)
        store = runtime.GraphStore(max_entries=8)
        kw = dict(backend=backend, plan="autotune", tune_budget=budget,
                  tune_timeout_s=TIMEOUT_S, max_shard_n=MAX_SHARD_N,
                  store=store, graph_key=prof.name)

        t0 = time.perf_counter()
        exe = runtime.compile(spec, ds, **kw)
        tune_s = time.perf_counter() - t0
        rep = exe.tune_report

        before = runtime.tune_cache_stats()["measurements"]
        exe2 = runtime.compile(spec, ds, **kw)
        remeasured = runtime.tune_cache_stats()["measurements"] - before

        rows.append({
            "graph": prof.name, "arch": ARCH, "backend": backend,
            "plan_source": exe.plan_source, "scale": scale,
            "nodes": prof.num_nodes, "edges": int(ds.edges.shape[0]),
            "analytic_ms": rep["analytic_ms"],
            "autotuned_ms": rep["winner_ms"],
            "speedup": rep["speedup"],
            "winner_config": rep["winner_config"],
            "candidates_measured": rep["candidates_measured"],
            "candidates_failed": rep["candidates_failed"],
            "tune_wall_s": round(tune_s, 2),
            "winner_cache_hit": bool(remeasured == 0
                                     and exe2.plan == exe.plan),
        })
        print(f"[autotune] {prof.name} ({backend}): analytic "
              f"{rep['analytic_ms']} ms -> winner {rep['winner_ms']} ms "
              f"({rep['speedup']}x, {rep['candidates_measured']} measured, "
              f"{rep['candidates_failed']} failed; cache hit on recompile: "
              f"{rows[-1]['winner_cache_hit']})")

    merge_bench_json("autotune", {
        "backend": backend, "arch": ARCH, "budget": budget,
        "env": env.describe(), "rows": rows})
    derived = {
        "min_speedup": min(r["speedup"] for r in rows),
        "max_speedup": max(r["speedup"] for r in rows),
        "all_cache_hits": all(r["winner_cache_hit"] for r in rows),
        "recorded": "BENCH_gnn.json",
    }
    return rows, derived


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--budget", type=int, default=BUDGET)
    ap.add_argument("--backend", default=BACKEND,
                    choices=["pallas", "jax", "reference"])
    args = ap.parse_args()

    from repro import env
    env.pin_for_benchmarks()
    env.enable_compile_cache()
    rows, derived = bench_gnn_autotune(budget=args.budget,
                                       backend=args.backend)
    print(derived)


if __name__ == "__main__":
    main()
