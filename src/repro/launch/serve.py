"""Production serving launcher: one scheduler-driven path for both engines.

Both modes build a continuous-batching :class:`repro.serving.Server` over
their engine (the LM ``ServeEngine`` streams by prompt length, the GNN
``GNNServeEngine`` by (model, graph)); requests go in as tickets with
optional priority/deadline, micro-batches form under the hybrid
max-batch-size + max-wait policy, and outcomes come back typed
(Completed / Rejected / Expired) with per-request queue/engine latency.

LM generation (default)::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
        --num-requests 8 --prompt-len 32 --new-tokens 32

GNN node classification (repro.gnn zoo + GNNServeEngine)::

    PYTHONPATH=src python -m repro.launch.serve --mode gnn \
        --graphs cora,citeseer --models gcn,gat --num-requests 64

Multi-device GNN serving (sharded Executables via repro.dist.gnn)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --mode gnn --mesh 8 \
        --model-parallel 2 --graphs cora --models gcn --backend reference
"""
from __future__ import annotations

import argparse
import time

import numpy as np

# NOTE: repro.serving (and through it jax + the model stack) is imported
# inside the helpers, keeping `--help` / arg errors fast.


def _make_server(engine, args):
    from repro.serving import SchedulerConfig, Server

    return Server(engine, SchedulerConfig(
        max_batch_size=args.batch_size,
        max_wait_ms=args.max_wait_ms,
        max_queue_depth=args.queue_depth))


def _submit(server, payload, stats: dict, **kw):
    """Closed-loop submit: on queue-full backpressure, drive the scheduler
    to make room and retry instead of silently dropping the request.
    Retries are counted in ``stats`` (each one shows up in the server's
    submitted/rejected totals)."""
    from repro.serving import Rejected

    while True:
        ticket = server.submit(payload, **kw)
        out = ticket.poll()
        if not (isinstance(out, Rejected) and out.kind == "backpressure"):
            return ticket
        if server.step(force=True) == 0:
            return ticket           # no progress possible; keep the reject
        stats["retries"] = stats.get("retries", 0) + 1


def _resolve(server, tickets) -> list:
    """Drain the scheduler and collect outcomes (submission order)."""
    server.drain()
    return [t.result() for t in tickets]


def _report(server, stats: dict) -> str:
    line = server.report()
    if stats.get("retries"):
        line += (f" | {stats['retries']} backpressure retries "
                 f"(counted in submitted/rejected)")
    return line


def _latency_line(outcomes) -> str:
    from repro.serving import Completed

    lat = [o.latency_ms for o in outcomes if isinstance(o, Completed)]
    if not lat:
        return "no completed requests"
    p50, p95, p99 = np.percentile(lat, [50, 95, 99])
    return f"latency p50 {p50:.2f} ms, p95 {p95:.2f} ms, p99 {p99:.2f} ms"


def _serve_lm(args) -> None:
    import jax

    from repro.configs.registry import get_config, get_smoke
    from repro.models import lm
    from repro.serving import Completed
    from repro.serving.engine import Request, ServeEngine

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    if cfg.input_mode != "tokens":
        raise SystemExit(f"{args.arch} needs frontend embeddings; serve "
                         f"token archs (see examples/serve_lm.py)")
    params = lm.init_params(cfg, jax.random.key(0))
    engine = ServeEngine(cfg, params,
                         max_len=args.prompt_len + args.new_tokens + 1)
    server = _make_server(engine, args)

    rng = np.random.default_rng(0)
    shape = (args.prompt_len, cfg.n_codebooks) if cfg.n_codebooks > 1 \
        else (args.prompt_len,)
    stats: dict = {}
    t0 = time.time()
    tickets = [_submit(
        server,
        Request(rng.integers(0, cfg.vocab_size, shape).astype(np.int32),
                max_new_tokens=args.new_tokens,
                temperature=args.temperature),
        stats)
        for _ in range(args.num_requests)]
    outcomes = _resolve(server, tickets)
    dt = time.time() - t0

    done = [o for o in outcomes if isinstance(o, Completed)]
    served = sum(o.value.shape[0] for o in done)
    print(_report(server, stats))
    print(_latency_line(outcomes))
    print(f"served {len(done)}/{args.num_requests} requests, {served} "
          f"tokens in {dt:.2f}s ({served / dt:.1f} tok/s)")


def _serve_gnn(args) -> None:
    from repro.gnn.models import ZooSpec
    from repro.graphs.datasets import make_dataset
    from repro.serving import Completed
    from repro.serving.gnn_engine import GNNServeEngine, NodeRequest

    graphs = [g.strip() for g in args.graphs.split(",") if g.strip()]
    models = [m.strip() for m in args.models.split(",") if m.strip()]

    from repro.graphs.datasets import DATASETS

    mesh = None
    if args.mesh:
        from repro.dist.gnn import SUPPORTED_ARCHS
        from repro.launch.mesh import mesh_from_cli

        bad = [m for m in models if m not in SUPPORTED_ARCHS]
        if bad:
            raise SystemExit(
                f"--mesh serving supports {SUPPORTED_ARCHS}; drop {bad} "
                f"from --models")
        mesh = mesh_from_cli(args.mesh, args.model_parallel)
        print(f"mesh: {args.mesh} devices as "
              f"data={args.mesh // args.model_parallel} x "
              f"model={args.model_parallel} (sharded Executables)")

    if args.plan == "autotune":
        print(f"plan source: autotune (budget {args.tune_budget} candidates "
              f"per (model, graph); winners memoized via REPRO_PLAN_CACHE)")
    if mesh is not None and args.partition != "contiguous":
        print(f"partition: {args.partition} (hub cache "
              f"{args.hub_cache} rows)")
    engine = GNNServeEngine(max_shard_n=args.shard_n, backend=args.backend,
                            mesh=mesh, partition=args.partition,
                            hub_cache=args.hub_cache, plan=args.plan,
                            tune_budget=args.tune_budget)
    datasets = {}
    for g in graphs:
        # pre-check against the engine's densification limit BEFORE paying
        # for edge generation (full reddit: ~115M edges, minutes of work)
        est_nodes = int(DATASETS[g].num_nodes * args.scale)
        if est_nodes ** 2 * 4 > engine.max_dense_gib * 2 ** 30:
            raise SystemExit(
                f"graph {g!r} at scale {args.scale} (~{est_nodes} nodes) "
                f"exceeds the {engine.max_dense_gib} GiB dense-shard limit; "
                f"pass a smaller --scale")
        ds = make_dataset(g, seed=0, scale=args.scale)
        datasets[g] = ds
        engine.register_graph(g, ds)
        print(f"graph {g}: {ds.profile.num_nodes} nodes, "
              f"{ds.edges.shape[0]} edges, {ds.profile.feature_dim} features")

    for g in graphs:
        prof = datasets[g].profile
        for m in models:
            engine.register_model(
                f"{m}@{g}",
                ZooSpec(m, prof.feature_dim, args.hidden, prof.num_classes,
                        num_layers=args.layers, heads=args.heads),
                seed=0)

    server = _make_server(engine, args)
    rng = np.random.default_rng(1)
    stats: dict = {}
    t0 = time.time()
    tickets = []
    for i in range(args.num_requests):
        g = graphs[int(rng.integers(len(graphs)))]
        m = models[int(rng.integers(len(models)))]
        n = datasets[g].profile.num_nodes
        ids = rng.integers(0, n, size=int(rng.integers(1, args.nodes_per_req + 1)))
        tickets.append(_submit(
            server, NodeRequest(graph=g, node_ids=ids, model=f"{m}@{g}"),
            stats,
            priority=1 if i % 8 == 0 else 0,
            deadline_ms=args.deadline_ms))
    outcomes = _resolve(server, tickets)
    dt = time.time() - t0

    done = [o.value for o in outcomes if isinstance(o, Completed)]
    for p in done[:4]:
        print(f"  {p.model} on {p.graph}: nodes {p.node_ids[:5].tolist()} -> "
              f"classes {p.classes[:5].tolist()} "
              f"(p={np.round(p.probs[:5], 3).tolist()})")
    print(engine.cache_report())
    print(_report(server, stats))
    print(_latency_line(outcomes))
    print(f"served {len(done)}/{len(tickets)} requests in {dt:.2f}s "
          f"({len(done) / dt:.1f} req/s)")
    missed = [o for o in outcomes if not isinstance(o, Completed)]
    if missed:
        raise SystemExit(f"{len(missed)} of {len(tickets)} requests were "
                         f"not completed; first: {missed[0]}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["lm", "gnn"], default="lm")
    # shared scheduler policy
    ap.add_argument("--batch-size", type=int, default=4,
                    help="scheduler max micro-batch size")
    ap.add_argument("--max-wait-ms", type=float, default=0.0,
                    help="oldest-entry wait that dispatches an underfull "
                         "batch (0 = dispatch immediately)")
    ap.add_argument("--queue-depth", type=int, default=256,
                    help="per-stream admission bound (backpressure)")
    # LM path
    ap.add_argument("--arch", default="qwen3-8b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--num-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    # GNN path
    ap.add_argument("--graphs", default="cora")
    ap.add_argument("--models", default="gcn,gat")
    ap.add_argument("--backend", default=None,
                    choices=["pallas", "jax", "reference"],
                    help="kernel backend pinned into each compiled "
                         "Executable (default: REPRO_KERNEL_BACKEND env, "
                         "else pallas)")
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--heads", type=int, default=2)
    ap.add_argument("--mesh", type=int, default=0, metavar="DEVICES",
                    help="serve from sharded Executables on a (data, "
                         "model) mesh over this many devices (0 = single "
                         "device; on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first)")
    ap.add_argument("--partition", choices=["contiguous", "fennel"],
                    default="contiguous",
                    help="data-axis placement for --mesh serving: "
                         "contiguous dst-row ranges, or the fennel "
                         "locality partitioner + hub cache")
    ap.add_argument("--hub-cache", type=int, default=256,
                    help="--partition fennel: top-k out-degree vertices "
                         "replicated to every data group (0 disables)")
    ap.add_argument("--model-parallel", type=int, default=2,
                    help="model-axis size of the --mesh (data axis = "
                         "devices / model_parallel)")
    ap.add_argument("--plan", choices=["analytic", "autotune"],
                    default="analytic",
                    help="layer-plan source: Table-I cost model, or "
                         "measured winners from the repro.tune autotuner")
    ap.add_argument("--tune-budget", type=int, default=8,
                    help="--plan autotune: max candidate plans measured "
                         "per (model, graph)")
    ap.add_argument("--shard-n", type=int, default=512)
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--nodes-per-req", type=int, default=8)
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request deadline; queued past it -> Expired")
    args = ap.parse_args()

    from repro import env
    env.enable_compile_cache()

    if args.mode == "gnn":
        _serve_gnn(args)
    else:
        _serve_lm(args)


if __name__ == "__main__":
    main()
