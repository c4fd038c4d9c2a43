#!/usr/bin/env python3
"""Readings the limits in ``limits/<cell>.json`` are set from.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 \\
        --seconds 5 [--controls bfloat16,int8] [--faults] --out <file.jsonl>

For each seed, in one process and on the chip: the cell's set-up, a short
window at the cell's own load, then the compared numbers three ways: of
the program (sound runs, the lower reading), of each control (the plain
reference computed in that precision in the program's place, the upper
reading) and, with ``--faults`` on a training cell, of each planted fault
(the reference put in the program's place with half the batch left out;
a state left unchanged reads 1 by construction). One JSON line per seed.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.harness import common, graphgen, reference  # noqa: E402
from bench.run import Context  # noqa: E402


def fault_readings(driver, ctx) -> dict:
    """The numbers of the training cell's planted faults."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    train = common.load_module(ctx.cell.driver_path)
    cfg, g = ctx.cell.config, ctx.graph
    p0 = jax.tree.map(jnp.asarray, driver.p0)
    ref = reference.train_steps(ctx.ref_mod, cfg, p0, g, train.CHECK_STEPS)
    half = g.train_mask & (np.arange(g.num_nodes) < g.num_nodes // 2)
    out = {"half_batch": train.numbers(reference.train_steps(
        ctx.ref_mod, cfg, p0, g, train.CHECK_STEPS, mask=half), ref,
        driver.p0)}
    unchanged = {"losses": [ref["losses"][0]] * train.CHECK_STEPS,
                 "grads": ref["grads"], "params": driver.p0}
    out["unchanged"] = train.numbers(unchanged, ref, driver.p0)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--mutation-rate", type=float, default=None,
                    help="serve cells: the traffic's mutation rate instead "
                         "of the committed one")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    cell = common.resolve(args.workload)
    if args.mutation_rate is not None:
        cell.traffic["mutation_rate_per_s"] = args.mutation_rate
    common.device_info(cell.chips)
    common.enable_compile_cache()
    controls = [c for c in args.controls.split(",") if c]
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        cfg = cell.config
        graph = graphgen.benchmark_graph(cfg["graph"], seed)
        ref_mod = common.load_module(cell.reference_path)
        ctx = Context(cell=cell, seed=seed, seconds=args.seconds,
                      graph=graph, params=reference.init_params(
                          ref_mod, cfg, seed), ref_mod=ref_mod)
        driver = common.load_module(cell.driver_path).Run(ctx)
        driver.setup()
        rec = driver.window(args.seconds)
        driver.release()
        gc.collect()
        row = {"workload": cell.name, "seed": seed,
               "attempted": rec["attempted"], "failed": rec["failed"],
               "e2e": rec["e2e"], "program": driver.check(),
               "controls": {c: driver.check(control=c) for c in controls}}
        if args.faults:
            row["faults"] = fault_readings(driver, ctx)
        row["seconds"] = time.perf_counter() - t0
        line = json.dumps(row)
        print(line, flush=True)
        with out.open("a") as f:
            f.write(line + "\n")
        del driver, ctx
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
