"""GNN serving benchmark: requests/sec + latency percentiles of the
serving stack across the three Table-II citation graphs, recorded to
BENCH_gnn.json.

Three regimes:
  * cold    — first request per (model, graph): compiles the Executable
              (plan + shard + jit; under ``--plan autotune`` also the
              candidate measurements) and runs full-graph inference (the
              amortized unit of work).
  * warm    — steady-state request stream answered from the Executable's
              cached full-graph softmax (GNNIE's \"accelerator wins become
              end-user wins\" path).
  * poisson — open-loop Poisson arrivals through the continuous-batching
              Server on a simulated arrival clock (engine service time is
              real measured wall time), recording p50/p95/p99 end-to-end
              latency (queue + engine) and the peak queue depth the
              scheduler absorbed. Run on cora at ~80% of the measured warm
              throughput, so queueing is real but stable.

The sweep covers both backends: reference rows (pure jnp, full-scale
graphs) measure the serving stack itself; pallas rows run the same stack
through the Pallas kernels (interpret mode off-TPU, hence the reduced
graph scales). Every row records its backend and plan source.

    PYTHONPATH=src python -m benchmarks.gnn_serve \
        --backends reference,pallas --plan autotune
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.report import merge_bench_json

# (name, scale) per backend: pubmed's densified (S·n)² grid at full scale
# is ~1.5 GiB, too big for a CPU smoke benchmark; the pallas rows shrink
# further because interpret mode pays a large per-element cost (citeseer
# hardest: its 3703-dim features dominate).
GRAPHS = {
    "reference": (("cora", 1.0), ("citeseer", 1.0), ("pubmed", 0.15)),
    "pallas": (("cora", 0.25), ("citeseer", 0.15), ("pubmed", 0.05)),
}
SHARD_N = {"reference": 512, "pallas": 256}
WARM_REQUESTS = 256
POISSON_REQUESTS = 512
POISSON_BATCH = 8
DEFAULT_BACKENDS = ("reference", "pallas")


def _poisson_regime(engine, graph: str, num_nodes: int,
                    rate_rps: float) -> dict:
    """Open-loop arrivals at ``rate_rps`` through the Server.

    The Server runs on a simulated clock: each arrival advances the clock
    to its (virtual) arrival time, each engine step advances it by the
    step's real measured wall time — so queueing delay is what a single
    busy server would actually accumulate at that offered load,
    independent of how fast this harness loops.
    """
    from repro.serving import Completed, SchedulerConfig, Server

    from repro.serving.gnn_engine import NodeRequest

    clk = {"now": 0.0}
    server = Server(engine,
                    SchedulerConfig(max_batch_size=POISSON_BATCH,
                                    max_queue_depth=4096),
                    clock=lambda: clk["now"])
    rng = np.random.default_rng(1)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps,
                                         size=POISSON_REQUESTS))
    tickets = []
    i = 0
    while i < len(arrivals) or server.queue_depth() > 0:
        if server.queue_depth() == 0 and i < len(arrivals):
            clk["now"] = max(clk["now"], arrivals[i])   # idle: jump ahead
        while i < len(arrivals) and arrivals[i] <= clk["now"]:
            ids = rng.integers(0, num_nodes, size=8)
            # stamp the ticket at its virtual arrival, not the post-step
            # clock: wait accrued while the engine was busy must count
            # (submissions are in arrival order, so this is monotone)
            t_now, clk["now"] = clk["now"], arrivals[i]
            tickets.append(server.submit(NodeRequest(graph, ids,
                                                     model="gcn")))
            clk["now"] = t_now
            i += 1
        t0 = time.perf_counter()
        n = server.step(force=True)
        if n:                       # engine busy time passes on the clock
            clk["now"] += time.perf_counter() - t0

    lat = [o.latency_ms for o in (t.result() for t in tickets)
           if isinstance(o, Completed)]
    p50, p95, p99 = np.percentile(lat, [50, 95, 99])
    m = server.metrics()
    return {
        "rate_rps": round(rate_rps, 1), "requests": POISSON_REQUESTS,
        "max_batch_size": POISSON_BATCH,
        "p50_ms": round(float(p50), 3), "p95_ms": round(float(p95), 3),
        "p99_ms": round(float(p99), 3),
        "peak_queue_depth": m["peak_queue_depth"],
        "batches": m["batches"],
        "mean_batch": round(m["dispatched"] / m["batches"], 2),
    }


def bench_gnn_serve(backends=DEFAULT_BACKENDS, plan: str = "analytic",
                    tune_budget: int = 4):
    from repro.gnn.models import ZooSpec
    from repro.graphs.datasets import make_dataset
    from repro.serving.gnn_engine import GNNServeEngine, NodeRequest

    rows = []
    poisson = None
    for backend in backends:
        # reference rows always use the analytic plan (the tuner's winners
        # are environment-scoped per backend; the sweep's `plan` knob
        # targets the backend being tuned)
        be_plan = plan if backend != "reference" else "analytic"
        for name, scale in GRAPHS[backend]:
            ds = make_dataset(name, seed=0, scale=scale)
            prof = ds.profile
            engine = GNNServeEngine(max_shard_n=SHARD_N[backend],
                                    backend=backend, plan=be_plan,
                                    tune_budget=tune_budget)
            engine.register_graph(name, ds)
            engine.register_model("gcn",
                                  ZooSpec("gcn", prof.feature_dim, 16,
                                          prof.num_classes, num_layers=2))

            rng = np.random.default_rng(0)

            def req():
                ids = rng.integers(0, prof.num_nodes, size=8)
                return NodeRequest(name, ids, model="gcn")

            t0 = time.perf_counter()
            engine.serve([req()])
            cold_s = time.perf_counter() - t0

            t0 = time.perf_counter()
            engine.serve([req() for _ in range(WARM_REQUESTS)])
            warm_s = time.perf_counter() - t0
            warm_rps = WARM_REQUESTS / warm_s

            s = engine.stats
            rows.append({
                "graph": prof.name, "backend": backend,
                "plan_source": be_plan, "nodes": prof.num_nodes,
                "edges": int(ds.edges.shape[0]), "scale": scale,
                "cold_ms": round(cold_s * 1e3, 2),
                "warm_req_per_s": round(warm_rps, 1),
                "logits_cache_hits": s["logits_cache_hits"],
                "logits_cache_misses": s["logits_cache_misses"],
            })
            if backend == "reference" and name == "cora":
                poisson = _poisson_regime(engine, name, prof.num_nodes,
                                          rate_rps=0.8 * warm_rps)

    merge_bench_json("gnn_serve", {
        "backends": list(backends), "plan": plan,
        "warm_requests": WARM_REQUESTS, "rows": rows, "poisson": poisson})
    ref_rows = [r for r in rows if r["backend"] == "reference"]
    derived = {"min_warm_rps": min(r["warm_req_per_s"]
                                   for r in (ref_rows or rows)),
               "backends": "+".join(backends),
               "poisson_p99_ms": poisson["p99_ms"] if poisson else None,
               "poisson_peak_queue": (poisson["peak_queue_depth"]
                                      if poisson else None),
               "recorded": "BENCH_gnn.json"}
    return rows, derived


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--backends", default=",".join(DEFAULT_BACKENDS),
                    help="comma list of kernel backends to sweep")
    ap.add_argument("--plan", choices=["analytic", "autotune"],
                    default="analytic",
                    help="plan source for non-reference backends")
    ap.add_argument("--tune-budget", type=int, default=4)
    args = ap.parse_args()

    from repro import env
    env.pin_for_benchmarks()
    env.enable_compile_cache()
    backends = tuple(b.strip() for b in args.backends.split(",") if b.strip())
    rows, derived = bench_gnn_serve(backends=backends, plan=args.plan,
                                    tune_budget=args.tune_budget)
    for r in rows:
        print(r)
    print(derived)


if __name__ == "__main__":
    main()
