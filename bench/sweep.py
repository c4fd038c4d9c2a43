#!/usr/bin/env python3
"""Find the serve cell's sustainable mutation rate: one set-up, then one
window per mutation rate at the cell's request rate.

    python bench/sweep.py --workload gcn-pubmed.serve-mutate --seed 7 \\
        --seconds 15 --rates 0,2,4,8

Prints one JSON line per rate: mutations applied, their latency (p50, p95)
and how late the mutation generator ran in each quarter of the window (a
lateness that climbs from quarter to quarter is a growing backlog), with
the request latency p95 and the device forwards.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.harness import common, graphgen, reference  # noqa: E402
from bench.run import Context  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = common.resolve(args.workload)
    common.device_info(cell.chips)
    common.enable_compile_cache()
    cfg = cell.config
    graph = graphgen.benchmark_graph(cfg["graph"], args.seed)
    ref_mod = common.load_module(cell.reference_path)
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds,
                  graph=graph, params=reference.init_params(
                      ref_mod, cfg, args.seed), ref_mod=ref_mod)
    driver = common.load_module(cell.driver_path).Run(ctx)
    driver.setup()
    for rate in (float(r) for r in args.rates.split(",")):
        driver.plan(args.seconds, rate)
        rec = driver.window(args.seconds)
        log = driver.last["mut_log"]
        quarters = [[late * 1e3 for t, late, _, _ in log
                     if q * args.seconds / 4 <= t < (q + 1) * args.seconds / 4]
                    for q in range(4)]
        mlat = [ret * 1e3 for _, _, ret, _ in log]
        print(json.dumps({
            "mutation_rate_per_s": rate, "mutations": len(log),
            "failed": rec["failed"],
            "mutate_p50_ms": common.percentile(mlat, 50),
            "mutate_p95_ms": common.percentile(mlat, 95),
            "late_ms_by_quarter": [sum(q) / len(q) if q else None
                                   for q in quarters],
            "serve_p95_ms": rec["e2e"]["serve_p95_ms"],
            "counters": rec["counters"], "notes": rec["notes"]}), flush=True)
    driver.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
