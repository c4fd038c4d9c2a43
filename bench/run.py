#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of BENCHMARK.json's ``workloads``. Everything else is
found by name: the configuration file it names, ``traffic/<traffic>.json``,
the driver of the traffic's kind (``drivers/<kind>.py``), the plain
reference of the configuration's architecture (``references/<arch>.py``),
the limits of the compared numbers (``limits/<cell>.json``) and, with
``--trace 1``, one reader per per-layer metric (``metrics/<name>.py``).

A run makes the graph and the weights from the seed, lets the driver build
and warm the program (set-up), measures for ``--seconds``, reads the peak
device memory, frees the program and compares what the timed path produced
with the reference. With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics read from a
profiler trace of the window. It exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.harness import common, graphgen, reference, trace  # noqa: E402
from bench.harness import work  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a driver is handed: the cell, the seed, the window's length, the
    data and weights the benchmark made, and the hooks a fault test plants under the timed
    path (empty in every benchmark run)."""

    cell: common.Cell
    seed: int
    seconds: float
    graph: graphgen.Graph
    params: object
    ref_mod: object
    hooks: dict = dataclasses.field(default_factory=dict)

    def hook(self, name: str, fn):
        return self.hooks[name](fn) if name in self.hooks else fn

    @staticmethod
    def span(name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def _cpu_device(chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def run_cell(cell: common.Cell, seed: int, seconds: float, traced: bool, *,
             require_tpu: bool = True, hooks: dict | None = None,
             t_start: float | None = None) -> dict:
    """One run of ``cell``; returns the result object. ``require_tpu=False``
    and ``hooks`` are for tests on the CPU: the look for a chip is skipped
    and the hooks break the timed path."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    device = common.device_info(cell.chips) if require_tpu \
        else _cpu_device(cell.chips)
    if require_tpu:
        common.enable_compile_cache()
    counter = common.CompileCounter()
    cfg = cell.config
    graph = graphgen.benchmark_graph(cfg["graph"], seed)
    ref_mod = common.load_module(cell.reference_path)
    params = reference.init_params(ref_mod, cfg, seed)
    ctx = Context(cell=cell, seed=seed, seconds=seconds, graph=graph, params=params,
                  ref_mod=ref_mod, hooks=hooks or {})
    driver = common.load_module(cell.driver_path).Run(ctx)
    driver.setup()
    setup_s = time.perf_counter() - t_start
    log(f"[setup] {cell.name} seed {seed}: {setup_s:.3f} s")

    # set-up's objects leave the collector's view, so that a collection in
    # the window scans only what the window makes
    gc.collect()
    gc.freeze()
    loads0, hits0 = counter.snapshot()
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        with Context.span(trace.WINDOW) if traced else contextlib.nullcontext():
            rec = driver.window(seconds)
    finally:
        if traced:
            jax.profiler.stop_trace()
    loads1, hits1 = counter.snapshot()
    log(f"[window] {rec['window_s']:.3f} s, {rec['attempted']} attempted, "
        f"{rec['failed']} failed; programs loaded in the window "
        f"{loads1 - loads0}, of them from the compile cache "
        f"{hits1 - hits0}, compiled {loads1 - loads0 - (hits1 - hits0)}")
    for line in rec.get("notes", []):
        log(f"[window] {line}")
    if require_tpu:
        device["memory_peak_bytes"] = common.peak_memory_bytes(cell.chips)

    result: dict = {}
    if traced:
        red = trace.reduce(trace.extract(tdir))
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        log(f"[trace] busy {red['busy_s']:.6f} s of {red['window_s']:.6f} s,"
            f" Pallas {red['pallas_s']:.6f} s, {red['ops']} device ops")
    else:
        red = None

    gc.unfreeze()
    driver.release()
    gc.collect()
    checks = driver.check()
    limits = cell.limits
    missing = sorted(set(checks) - set(limits))
    if missing:
        raise KeyError(f"no limit for compared numbers {missing} in "
                       f"limits/{cell.name}.json")
    correct = rec["failed"] == 0 and all(
        checks[k] <= limits[k] for k in checks)

    if traced:
        peak = work.peak(device["kind"]) if require_tpu else None
        rctx = {"cell": cell.name, "config": cfg, "ref_mod": ref_mod,
                "counters": rec["counters"], "window_s": rec["window_s"],
                "trace": red, "peak": peak}
        metrics = {}
        for m in cell.per_layer:
            value = common.metric_reader(m["name"])(rctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": rec["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}

    out = {"correct": bool(correct), "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    out.update(result)
    out["checks"] = {k: {"value": _finite(v), "limit": limits[k]}
                     for k, v in checks.items()}
    for k, v in checks.items():
        log(f"check {k} {v!r} limit {limits[k]!r}")
    return out


def _finite(v: float) -> float:
    """A compared number as JSON can hold it: a gap that could not be
    computed (non-finite output, wrong shape) reads 1e30."""
    return v if v == v and abs(v) < 1e30 else 1e30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = common.resolve(args.workload)
    try:
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START)
    except common.NoAccelerator as err:
        log(f"bench/run.py: {err}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
