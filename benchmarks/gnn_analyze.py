"""Static-analysis benchmark: what the analyzer costs, and what its
pruning saves, recorded to BENCH_gnn.json (`analyze` section).

Two measurements:

  * **gate** — wall time of every ``repro.analyze`` pass exactly as the
    CI gate runs them (``launch.analyze.build_report`` with the dynamic
    retrace probes on), per-pass and total, plus the finding counts
    (which must be zero on a healthy checkout).
  * **autotune_pruning** — one real autotune run on a Table-II graph:
    candidates measured vs statically pruned, the mean measure cost per
    candidate, and the estimated measure time the pruning saved
    (pruned candidates are execution-identical or illegal, so each one
    skipped is one full compile+measure loop that was never paid).

    PYTHONPATH=src python -m benchmarks.gnn_analyze --budget 6
"""
from __future__ import annotations

import argparse
import json
import time

from benchmarks.report import merge_bench_json

GRAPH, SCALE = "cora", 0.25
ARCH = "gcn"
BUDGET = 6
REPS = 3
MAX_SHARD_N = 128


def bench_analyze(budget: int = BUDGET, reps: int = REPS) -> dict:
    from repro import runtime, tune
    from repro.gnn.models import ZooSpec
    from repro.graphs.datasets import make_dataset
    from repro.kernels.registry import get_backend
    from repro.launch.analyze import build_report

    # -- the CI gate's cost on this checkout -------------------------------
    t0 = time.perf_counter()
    report = build_report(probe=True)
    gate_s = time.perf_counter() - t0
    gate = {
        "total_s": round(gate_s, 3),
        "pass_ms": {k: round(v, 1) for k, v in report.timings_ms.items()},
        "findings": {s: report.count(s)
                     for s in ("error", "warning", "info")},
        "skipped": sorted(report.skipped),
    }

    # -- measure time saved by static pruning ------------------------------
    runtime.clear_tune_cache()
    ds = make_dataset(GRAPH, seed=0, scale=SCALE)
    spec = ZooSpec(ARCH, ds.profile.feature_dim, 16, ds.profile.num_classes,
                   num_layers=2)
    t0 = time.perf_counter()
    rec = tune.autotune_plan(spec, ds.edges, ds.profile.num_nodes,
                             backend=get_backend("reference"),
                             features=ds.features, max_n=MAX_SHARD_N,
                             budget=budget, reps=reps)
    tune_s = time.perf_counter() - t0
    rep = rec.report()
    measured = rep["candidates_measured"]
    per_candidate_s = tune_s / max(measured, 1)
    pruning = {
        "graph": GRAPH, "scale": SCALE, "arch": ARCH,
        "budget": budget, "reps": reps,
        "tune_s": round(tune_s, 3),
        "candidates_measured": measured,
        "candidates_failed": rep["candidates_failed"],
        "candidates_pruned": rep["candidates_pruned"],
        "pruned_reasons": rep["pruned_reasons"],
        "per_candidate_s": round(per_candidate_s, 3),
        "est_measure_time_saved_s":
            round(rep["candidates_pruned"] * per_candidate_s, 3),
    }

    payload = {"gate": gate, "autotune_pruning": pruning}
    merge_bench_json("analyze", payload)
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--budget", type=int, default=BUDGET)
    ap.add_argument("--reps", type=int, default=REPS)
    args = ap.parse_args()
    print(json.dumps(bench_analyze(args.budget, args.reps), indent=2))


if __name__ == "__main__":
    main()
