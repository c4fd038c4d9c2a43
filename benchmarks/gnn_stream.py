"""Streaming-graph benchmark: mutate-while-serving + train-while-serve,
recorded to BENCH_gnn.json under the ``stream`` section.

The workload is the acceptance scenario: a 5%-of-edges mutation burst on
cora, applied as many SMALL :class:`~repro.graphs.delta.GraphDelta`\\ s
(streaming deltas arrive one event at a time — one big delta would both
be unrealistic and trivially invalidate everything), while a request
stream keeps hitting the server. Measured:

  * **mutation-apply latency** — ``Server.mutate`` wall time per delta
    (incremental shard patch + targeted invalidation + tensor push),
    p50/p95/max;
  * **targeted vs full invalidation** — the same burst twice, once per
    ``invalidation=`` mode: logits-cache hit rate of the interleaved
    request stream and the mean fraction of cached rows each delta
    drops (targeted must stay a minority; full drops 100% by
    construction);
  * **no-recompile** — the forward's jit trace count must not grow over
    the burst (graph tensors are jit *arguments*; slack slots keep the
    shapes) and ``graph_recompiles`` must stay 0;
  * **accuracy recovery** — from fresh weights, the
    :class:`~repro.stream.StreamTrainer` fine-tune loop (neighbor-sampled
    mini-batches seeded on the mutated neighborhoods, hot weight reloads)
    must reach >= 0.75 train accuracy while the server answers
    throughout.

    PYTHONPATH=src python -m benchmarks.gnn_stream
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.report import merge_bench_json

TARGET_ACC = 0.75


def _percentiles(xs) -> dict:
    xs = np.asarray(xs, dtype=np.float64)
    return {"p50_ms": float(np.percentile(xs, 50)),
            "p95_ms": float(np.percentile(xs, 95)),
            "max_ms": float(xs.max()),
            "mean_ms": float(xs.mean())}


def _run_burst(*, backend: str, scale: float, mode: str, n_deltas: int,
               seed: int, shard_n: int, train: bool) -> dict:
    """One full streaming run: warm the cache, apply the 5% burst as
    ``n_deltas`` small deltas with requests interleaved, optionally
    fine-tune to TARGET_ACC. Fresh engine + graph per run so targeted
    and full modes see the identical delta sequence."""
    from repro.gnn.models import ZooSpec
    from repro.graphs.datasets import make_dataset
    from repro.serving import Completed, SchedulerConfig, Server
    from repro.serving.gnn_engine import GNNServeEngine, NodeRequest
    from repro.stream import StreamTrainer, random_delta

    rng = np.random.default_rng(seed)
    ds = make_dataset("cora", seed=seed, scale=scale)
    n0 = ds.profile.num_nodes
    burst_ops = max(n_deltas, int(round(0.05 * ds.edges.shape[0])))
    per_delta = max(1, burst_ops // n_deltas)

    spec = ZooSpec("gcn", ds.profile.feature_dim, 16,
                   ds.profile.num_classes, num_layers=2)
    eng = GNNServeEngine(backend=backend, max_shard_n=shard_n,
                         streaming=True, invalidation=mode)
    eng.register_graph("cora", ds)
    eng.register_model("gcn", spec)
    srv = Server(eng, SchedulerConfig(max_batch_size=8))
    trainer = StreamTrainer(srv, graph="cora", model="gcn",
                            batch_nodes=32, fanout=(5, 5),
                            steps_per_round=20, lr=1e-2, seed=seed,
                            log=lambda s: None) if train else None

    # warm: one full sweep fills the logits cache
    tickets = [srv.submit(NodeRequest("cora", np.arange(n0), model="gcn"))]
    srv.drain()

    mutate_ms, inv_frac = [], []
    acc_trace = []
    for d in range(n_deltas):
        delta = random_delta(ds, rng, edge_ops=per_delta)
        rep = srv.mutate("cora", delta)
        mutate_ms.append(rep["mutate_ms"])
        (m,) = rep["executables"]
        if not m.get("recompile") and m.get("rows_cached"):
            inv_frac.append(m["rows_invalidated"] / m["rows_cached"])
        # the stream the cache serves between deltas
        for _ in range(4):
            ids = rng.integers(0, eng.graph_data("cora").profile.num_nodes,
                               size=8)
            tickets.append(srv.submit(
                NodeRequest("cora", ids, model="gcn")))
        srv.drain()
        if trainer is not None and (d + 1) % 5 == 0:
            acc_trace.append(trainer.round().get("train_acc"))

    # keep fine-tuning until the loop recovers (server still live)
    recovery_rounds = len(acc_trace)
    if trainer is not None:
        acc = trainer.train_accuracy()
        while acc < TARGET_ACC and recovery_rounds < 60:
            ids = rng.integers(0, eng.graph_data("cora").profile.num_nodes,
                               size=8)
            tickets.append(srv.submit(
                NodeRequest("cora", ids, model="gcn")))
            acc = trainer.round(force=True)["train_acc"]
            acc_trace.append(acc)
            recovery_rounds += 1
        srv.drain()

    outcomes = [t.result() for t in tickets]
    completed = sum(isinstance(o, Completed) for o in outcomes)
    s = eng.stats
    exe = eng._executables[("gcn", "cora")]
    l_tot = s["logits_cache_hits"] + s["logits_cache_misses"]
    out = {
        "invalidation": mode,
        "deltas": n_deltas,
        "edge_ops_total": per_delta * n_deltas,
        "burst_edge_fraction": per_delta * n_deltas / ds.edges.shape[0],
        "requests_completed": completed,
        "requests_submitted": len(tickets),
        "mutate_latency": _percentiles(mutate_ms),
        "rows_invalidated_frac_mean": (float(np.mean(inv_frac))
                                       if inv_frac else None),
        "rows_invalidated_frac_max": (float(np.max(inv_frac))
                                      if inv_frac else None),
        "logits_cache_hit_rate": (s["logits_cache_hits"] / l_tot
                                  if l_tot else None),
        "forward_traces": exe._jit_forward._cache_size(),
        "graph_recompiles": s["graph_recompiles"],
        "graph_patches": s["graph_patches"],
        "graph_patch_rebuilds": s["graph_patch_rebuilds"],
        "graph_patch_ms_total": s["graph_patch_ms_total"],
        "nodes_invalidated": s["nodes_invalidated"],
    }
    if trainer is not None:
        out["finetune"] = {
            "rounds": trainer.stats["rounds"],
            "steps": trainer.stats["steps"],
            "hot_reloads": trainer.stats["reloads"],
            "rebuilds": trainer.stats["rebuilds"],
            "step_traces": trainer.stats["step_traces"],
            "final_train_acc": trainer.train_accuracy(),
            "target_acc": TARGET_ACC,
            "recovered": trainer.train_accuracy() >= TARGET_ACC,
            "acc_trace": [round(a, 4) for a in acc_trace
                          if a is not None],
        }
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description="streaming-graph benchmark")
    ap.add_argument("--backend", default="reference")
    ap.add_argument("--scale", type=float, default=0.25)
    ap.add_argument("--deltas", type=int, default=50)
    ap.add_argument("--shard-n", type=int, default=256)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro import env
    env.enable_compile_cache()
    t0 = time.perf_counter()
    runs = {}
    for mode in ("targeted", "full"):
        print(f"[gnn_stream] {mode} burst ({args.deltas} deltas, "
              f"backend={args.backend}, scale={args.scale})...")
        runs[mode] = _run_burst(
            backend=args.backend, scale=args.scale, mode=mode,
            n_deltas=args.deltas, seed=args.seed, shard_n=args.shard_n,
            train=(mode == "targeted"))
        r = runs[mode]
        print(f"[gnn_stream]   mutate p50 {r['mutate_latency']['p50_ms']:.2f}"
              f" ms, invalidated frac mean "
              f"{r['rows_invalidated_frac_mean']:.3f}, "
              f"logits hit rate {r['logits_cache_hit_rate']:.3f}, "
              f"traces={r['forward_traces']}, "
              f"recompiles={r['graph_recompiles']}")
        if "finetune" in r:
            ft = r["finetune"]
            print(f"[gnn_stream]   fine-tune: {ft['rounds']} rounds -> "
                  f"train acc {ft['final_train_acc']:.3f} "
                  f"(recovered={ft['recovered']})")

    payload = {
        "benchmark": "gnn_stream",
        "graph": "cora",
        "scale": args.scale,
        "backend": args.backend,
        "arch": "gcn",
        "runs": runs,
        "targeted_vs_full_hit_rate": [
            runs["targeted"]["logits_cache_hit_rate"],
            runs["full"]["logits_cache_hit_rate"]],
        "wall_s": time.perf_counter() - t0,
    }
    merge_bench_json("stream", payload)
    print(f"[gnn_stream] wrote BENCH_gnn.json[stream] "
          f"({payload['wall_s']:.1f}s)")


if __name__ == "__main__":
    main()
