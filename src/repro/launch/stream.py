"""Streaming-graph launcher: mutate-while-serving + train-while-serve.

Drives the full ``repro.stream`` loop end to end on one graph/model pair:
a continuous-batching :class:`repro.serving.Server` answers node
requests while :func:`repro.stream.random_delta` mutations land through
``Server.mutate`` (incremental shard patching + targeted invalidation)
and a :class:`repro.stream.StreamTrainer` fine-tunes on the mutated
neighborhoods every ``--finetune-every`` mutations, hot-reloading
weights through ``Server.reload``.

CI smoke (reference backend, a minute on CPU)::

    PYTHONPATH=src python -m repro.launch.stream --steps 20 \
        --mutations 50 --backend reference

Multi-device serving (sharded Executables; the trainer stays
single-device mini-batch)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.stream --mesh 8 \
        --model-parallel 2 --backend reference
"""
from __future__ import annotations

import argparse
import time

import numpy as np

# jax + model stack imported inside run() to keep --help fast


def run(args) -> dict:
    from repro.analyze import lock_sanitizer
    from repro.gnn.models import ZooSpec
    from repro.graphs import make_dataset
    from repro.serving import Completed, SchedulerConfig, Server
    from repro.serving.gnn_engine import GNNServeEngine, NodeRequest
    from repro.stream import StreamTrainer, random_delta

    # REPRO_LOCKSAN=1 instruments every lock the stack creates below:
    # acquisition-order cycles (LS001) fail the run, long holds (LS002)
    # are reported — the streaming loop doubles as the sanitizer's
    # integration workload
    san = lock_sanitizer.current()
    if san is not None:
        print(f"[stream] lock sanitizer active "
              f"(hold threshold {san.hold_ms:.0f} ms)")

    rng = np.random.default_rng(args.seed)
    data = make_dataset(args.graph, scale=args.scale, seed=args.seed)
    prof = data.profile
    print(f"[stream] {args.graph}: {prof.num_nodes} nodes "
          f"{prof.num_edges} edges (scale={args.scale})")

    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh_for
        mesh = make_mesh_for(args.mesh, model_parallel=args.model_parallel)

    spec = ZooSpec(args.arch, prof.feature_dim, args.hidden,
                   prof.num_classes, num_layers=args.layers,
                   heads=args.heads)
    engine = GNNServeEngine(backend=args.backend, mesh=mesh,
                            max_shard_n=args.shard_n, streaming=True,
                            edge_slack=args.edge_slack,
                            invalidation=args.invalidation)
    engine.register_graph(args.graph, data)
    engine.register_model(args.arch, spec)
    server = Server(engine, SchedulerConfig(max_batch_size=args.batch_size))
    trainer = StreamTrainer(server, graph=args.graph, model=args.arch,
                            batch_nodes=args.batch_nodes,
                            fanout=tuple(args.fanout),
                            steps_per_round=args.steps, lr=args.lr,
                            seed=args.seed)

    t_start = time.perf_counter()
    tickets, outcomes = [], []
    served = 0
    for m in range(args.mutations):
        # traffic between mutations: the server must keep answering
        for _ in range(args.requests_per_mutation):
            ids = rng.integers(
                0, engine.graph_data(args.graph).profile.num_nodes,
                size=args.nodes_per_req)
            tickets.append(server.submit(
                NodeRequest(args.graph, ids, model=args.arch)))
        server.drain()

        delta = random_delta(data, rng, edge_ops=args.edge_ops,
                             p_node=args.p_node)
        rep = server.mutate(args.graph, delta)
        if args.verbose:
            print(f"[stream] mutation {m}: {delta.summary()} -> "
                  f"{rep['mutate_ms']:.1f} ms, "
                  f"{rep['executables']}")
        if (m + 1) % args.finetune_every == 0:
            trainer.round()

    server.drain()
    outcomes = [t.result() for t in tickets]
    done = [o for o in outcomes if isinstance(o, Completed)]
    served = len(done)
    wall_s = time.perf_counter() - t_start

    final_acc = trainer.train_accuracy()
    s = engine.stats
    print(f"[stream] {args.mutations} mutations, {served}/{len(tickets)} "
          f"requests completed in {wall_s:.1f}s")
    print(f"[stream] invalidation: {s['targeted_invalidations']} targeted "
          f"/ {s['full_invalidations']} full, "
          f"{s['nodes_invalidated']} rows dropped, "
          f"{s['graph_recompiles']} recompiles, "
          f"{s['graph_patches']} patches "
          f"({s['graph_patch_rebuilds']} rebuilds)")
    print(f"[stream] trainer: {trainer.stats['rounds']} rounds "
          f"({trainer.stats['steps']} steps, "
          f"{trainer.stats['reloads']} hot reloads, "
          f"step traces={trainer.stats['step_traces']}), "
          f"final train acc {final_acc:.3f}")
    print("[stream] " + engine.cache_report())

    ok = served == len(tickets) and served > 0
    if trainer.stats["rounds"] and trainer.stats["step_traces"] not in \
            (None, 1):
        print(f"[stream] WARNING: train step traced "
              f"{trainer.stats['step_traces']}x (expected 1)")
        ok = False
    lock_findings = []
    if san is not None:
        rep = san.report()
        lock_findings = [f.to_json() for f in rep.findings]
        if rep.findings:
            print(f"[stream] lock sanitizer:\n{rep.render()}")
        else:
            print(f"[stream] lock sanitizer: clean "
                  f"({san.acquisitions} acquisitions)")
        if rep.failed("error"):   # LS001 order cycle = deadlock hazard
            ok = False
    print(f"[stream] {'OK' if ok else 'FAILED'}")
    return {"ok": ok, "served": served, "submitted": len(tickets),
            "final_train_acc": final_acc, "engine_stats": dict(s),
            "trainer_stats": dict(trainer.stats), "wall_s": wall_s,
            "lock_findings": lock_findings}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--graph", default="cora")
    ap.add_argument("--arch", default="gcn")
    ap.add_argument("--scale", type=float, default=0.25,
                    help="dataset scale factor (1.0 = full profile)")
    ap.add_argument("--backend", default=None,
                    help="kernel backend (default: env/pallas)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--shard-n", type=int, default=512)
    ap.add_argument("--mesh", type=int, default=0, metavar="DEVICES",
                    help="serve on a (data, model) mesh of this many "
                         "devices (0 = single device)")
    ap.add_argument("--model-parallel", type=int, default=2)
    ap.add_argument("--batch-size", type=int, default=8)
    # mutation workload
    ap.add_argument("--mutations", type=int, default=50,
                    help="number of GraphDelta bursts to apply")
    ap.add_argument("--edge-ops", type=int, default=8,
                    help="edge insert/delete ops per delta")
    ap.add_argument("--p-node", type=float, default=0.1,
                    help="probability a delta also adds a node")
    ap.add_argument("--requests-per-mutation", type=int, default=4)
    ap.add_argument("--nodes-per-req", type=int, default=8)
    ap.add_argument("--edge-slack", type=float, default=0.25,
                    help="slack-slot fraction of the edge-list template")
    ap.add_argument("--invalidation", choices=["targeted", "full"],
                    default="targeted")
    # fine-tune cadence
    ap.add_argument("--steps", type=int, default=20,
                    help="optimizer steps per fine-tune round")
    ap.add_argument("--finetune-every", type=int, default=10,
                    help="fine-tune round every this many mutations")
    ap.add_argument("--batch-nodes", type=int, default=32)
    ap.add_argument("--fanout", type=int, nargs="+", default=[5, 5])
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args()

    from repro import env
    env.enable_compile_cache()

    out = run(args)
    raise SystemExit(0 if out["ok"] else 1)


if __name__ == "__main__":
    main()
