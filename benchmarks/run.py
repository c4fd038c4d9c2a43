"""Benchmark harness entry point: ``PYTHONPATH=src python -m benchmarks.run``.

One benchmark per paper table/figure (paper_tables.py), kernel
microbenchmarks (kernel_bench.py), and the roofline analysis over the
dry-run artifacts (roofline.py). Prints ``name,us_per_call,derived`` CSV
rows per the harness contract, with the detailed tables after.
"""
from __future__ import annotations

import sys
import time


def _csv(name: str, us: float, derived) -> None:
    print(f'{name},{us:.1f},"{derived}"')


def _run(name: str, fn, *args):
    t0 = time.perf_counter()
    rows, derived = fn(*args)
    us = (time.perf_counter() - t0) * 1e6
    _csv(name, us, derived)
    return rows, derived


def main() -> None:
    from repro import env
    env.pin_for_benchmarks()
    env.enable_compile_cache()

    from benchmarks.gnn_autotune import bench_gnn_autotune
    from benchmarks.gnn_serve import bench_gnn_serve
    from benchmarks.kernel_bench import bench_kernels
    from benchmarks.paper_tables import (bench_fig3, bench_fig4, bench_fig5,
                                         bench_table1, bench_table5)
    from benchmarks.roofline import bench_roofline, markdown_table
    from benchmarks.runtime_compile import bench_runtime_compile

    print("name,us_per_call,derived")
    all_rows = {}
    all_rows["table1_dataflow_costs"] = _run("table1_dataflow_costs", bench_table1)
    all_rows["fig3_gpu_speedup"] = _run("fig3_gpu_speedup", bench_fig3)
    all_rows["table5_vs_hygcn"] = _run("table5_vs_hygcn", bench_table5)
    all_rows["fig4_block_sweep"] = _run("fig4_block_sweep", bench_fig4)
    all_rows["fig5_scaling"] = _run("fig5_scaling", bench_fig5)
    all_rows["kernels"] = _run("kernels_microbench", bench_kernels)
    all_rows["gnn_serve"] = _run("gnn_serve", bench_gnn_serve)
    all_rows["runtime_compile"] = _run("runtime_compile",
                                       bench_runtime_compile)
    all_rows["gnn_autotune"] = _run("gnn_autotune", bench_gnn_autotune)
    all_rows["roofline"] = _run("roofline", bench_roofline)

    print("\n=== detailed tables ===", file=sys.stderr)
    for name, (rows, derived) in all_rows.items():
        print(f"\n--- {name}: {derived}", file=sys.stderr)
        if name != "roofline":
            for r in rows:
                print("   ", r, file=sys.stderr)
    ro_rows, _ = all_rows["roofline"]
    if ro_rows:
        print("\n--- roofline (single-pod) ---", file=sys.stderr)
        print(markdown_table(ro_rows, "single"), file=sys.stderr)


if __name__ == "__main__":
    main()
