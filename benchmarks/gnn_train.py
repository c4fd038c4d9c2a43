"""GNN training benchmark: step time, steps-to-accuracy, and hot-reload
latency into serving, recorded to BENCH_gnn.json (`gnn_train` section).

Three measurements:

  * **train** — full-batch `runtime.fit` training, one row per
    (graph, backend): mean/median step wall time after the first traced
    step, and the first step reaching the target train accuracy (the
    tier-1 acceptance threshold, 0.75). Reference rows run the Table-II
    graphs at full scale; pallas rows run cora scaled down (interpret
    mode off-TPU pays a large per-element cost) for a reduced step
    count, with the layer plan optionally autotuned (``--plan``).
  * **minibatch** — neighbor-sampled steps on cora (fixed-budget
    subgraphs, one jit trace): mean step time including the numpy
    sample+shard work, for comparison against the full-batch step.
  * **reload** — serving-side weight swap: ms to hot-reload trained
    params into a compiled Executable through ``Server.reload`` (no
    recompile), the first post-reload request (pays one full-graph
    softmax recompute), and a warm request after it.

    PYTHONPATH=src python -m benchmarks.gnn_train \
        --backends reference,pallas --plan autotune
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from benchmarks.report import merge_bench_json

# (graph, scale, steps) per backend
TRAIN_GRAPHS = {
    "reference": (("cora", 1.0, 200), ("citeseer", 1.0, 200)),
    "pallas": (("cora", 0.25, 8),),
}
SHARD_N = {"reference": 512, "pallas": 256}
ARCH = "gcn"
TARGET_ACC = 0.75
DEFAULT_BACKENDS = ("reference", "pallas")
MINIBATCH_STEPS = 30


def _trainable(ds, *, backend="reference", plan="analytic", tune_budget=4,
               max_shard_n=512, batch_nodes=0, fanout=(10, 5)):
    from repro import runtime
    from repro.gnn.models import ZooSpec
    from repro.graphs.sampler import NeighborSampler
    from repro.runtime.fit import TrainableExecutable
    from repro.training.optimizer import AdamWConfig

    spec = ZooSpec(ARCH, ds.profile.feature_dim, 16, ds.profile.num_classes)
    exe = runtime.compile(spec, ds, backend=backend, plan=plan,
                          tune_budget=tune_budget, max_shard_n=max_shard_n)
    sampler = None
    if batch_nodes:
        sampler = NeighborSampler(ds.edges, ds.profile.num_nodes,
                                  batch_nodes=batch_nodes, fanout=fanout,
                                  seed_ids=np.flatnonzero(ds.train_mask))
    opt = AdamWConfig(lr=1e-2, weight_decay=0.0, grad_clip=0.0,
                      schedule="constant", warmup_steps=0)
    return TrainableExecutable(exe, ds.labels, train_mask=ds.train_mask,
                               features=ds.features, opt_cfg=opt,
                               sampler=sampler)


def _run_steps(tr, steps: int):
    """Manual loop (instead of TrainLoop) so every step is timed and the
    per-step train accuracy is visible for steps-to-target."""
    params, opt = tr.params, tr.opt_state
    step_ms, accs = [], []
    for step in range(steps):
        batch = tr.data(step)
        t0 = time.perf_counter()
        params, opt, metrics = tr.step_fn(params, opt, batch)
        acc = float(metrics["acc"])
        step_ms.append((time.perf_counter() - t0) * 1e3)
        accs.append(acc)
    tr.params, tr.opt_state = params, opt
    tr.executable.update_params(params)
    return step_ms, accs


def bench_training(backends=DEFAULT_BACKENDS, plan="analytic",
                   tune_budget=4) -> list:
    from repro.graphs.datasets import make_dataset

    rows = []
    for backend in backends:
        be_plan = plan if backend != "reference" else "analytic"
        for name, scale, steps in TRAIN_GRAPHS[backend]:
            ds = make_dataset(name, seed=0, scale=scale)
            tr = _trainable(ds, backend=backend, plan=be_plan,
                            tune_budget=tune_budget,
                            max_shard_n=SHARD_N[backend])
            step_ms, accs = _run_steps(tr, steps)
            to_target = next((i for i, a in enumerate(accs)
                              if a >= TARGET_ACC), None)
            warm = step_ms[1:]   # step 0 pays the jit trace
            row = {
                "graph": ds.profile.name, "arch": ARCH, "backend": backend,
                "plan_source": tr.executable.plan_source, "scale": scale,
                "steps": steps,
                "trace_step_ms": round(step_ms[0], 3),
                "mean_step_ms": round(float(np.mean(warm)), 3),
                "p50_step_ms": round(float(np.median(warm)), 3),
                "final_train_acc": round(accs[-1], 4),
                "steps_to_target_acc": to_target,
                "target_acc": TARGET_ACC,
            }
            rows.append(row)
            print(f"[train] {row['graph']} ({backend}/{row['plan_source']}): "
                  f"{row['mean_step_ms']:.1f} ms/step, acc {accs[-1]:.3f}, "
                  f"{to_target} steps to {TARGET_ACC}")
    return rows


def bench_minibatch() -> dict:
    from repro.graphs.datasets import make_dataset

    ds = make_dataset("cora", seed=0)
    tr = _trainable(ds, batch_nodes=256, fanout=(10, 5))
    step_ms, accs = _run_steps(tr, MINIBATCH_STEPS)
    out = {
        "arch": ARCH, "backend": "reference", "plan_source": "analytic",
        "batch_nodes": 256, "fanout": [10, 5],
        "steps": MINIBATCH_STEPS,
        "trace_step_ms": round(step_ms[0], 3),
        "mean_step_ms": round(float(np.mean(step_ms[1:])), 3),
        "final_batch_acc": round(accs[-1], 4),
    }
    print(f"[minibatch] cora: {out['mean_step_ms']:.1f} ms/step "
          f"(sample+shard+update)")
    return out


def bench_reload() -> dict:
    """Weight-swap latency through the serving stack."""
    import jax

    from repro.gnn.models import ZooSpec, init_zoo
    from repro.graphs.datasets import make_dataset
    from repro.serving import Completed, SchedulerConfig, Server
    from repro.serving.gnn_engine import GNNServeEngine, NodeRequest

    ds = make_dataset("cora", seed=0)
    spec = ZooSpec(ARCH, ds.profile.feature_dim, 16, ds.profile.num_classes)
    engine = GNNServeEngine(backend="reference")
    engine.register_graph("cora", ds)
    engine.register_model("gcn", spec, seed=0)
    server = Server(engine, SchedulerConfig(max_batch_size=8))

    def one_request() -> float:
        t = server.submit(NodeRequest("cora", np.arange(8), model="gcn"))
        t0 = time.perf_counter()
        server.drain()
        ms = (time.perf_counter() - t0) * 1e3
        assert isinstance(t.result(), Completed)
        return ms

    cold_ms = one_request()
    warm_ms = float(np.median([one_request() for _ in range(5)]))

    new_params = init_zoo(jax.random.key(1), spec)
    t0 = time.perf_counter()
    server.reload(lambda eng: eng.reload_params("gcn", new_params))
    reload_ms = (time.perf_counter() - t0) * 1e3
    post_reload_ms = one_request()       # pays the softmax recompute
    rewarm_ms = float(np.median([one_request() for _ in range(5)]))

    out = {
        "backend": "reference", "plan_source": "analytic",
        "cold_request_ms": round(cold_ms, 3),
        "warm_request_ms": round(warm_ms, 3),
        "reload_ms": round(reload_ms, 3),
        "first_post_reload_request_ms": round(post_reload_ms, 3),
        "warm_post_reload_request_ms": round(rewarm_ms, 3),
        "compiles": engine.stats["compiles"],
        "logits_invalidations": engine.stats["logits_invalidations"],
    }
    print(f"[reload] swap {reload_ms:.2f} ms, first post-reload request "
          f"{post_reload_ms:.1f} ms (softmax recompute), warm "
          f"{rewarm_ms:.2f} ms; {out['compiles']} compile(s) total")
    return out


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
    payload = {
        "train": bench_training(backends=backends, plan=args.plan,
                                tune_budget=args.tune_budget),
        "minibatch": bench_minibatch(),
        "reload": bench_reload(),
    }
    merge_bench_json("gnn_train", payload)
    print("wrote gnn_train section to BENCH_gnn.json")


if __name__ == "__main__":
    main()
