"""Quickstart: train a zoo GNN on (synthetic) Cora through the runtime.

One ``runtime.fit()`` call compiles the model (the planner picks feature
block size B, shard grid, traversal order, fused vs two-stage per layer),
runs the jitted AdamW train step — full-batch by default, neighbor-sampled
mini-batches with ``--batch-nodes`` — and hands back the trained,
servable Executable.

    PYTHONPATH=src python examples/quickstart.py [--epochs 30] \
        [--backend reference] [--batch-nodes 256]
"""
import argparse
import sys
import time

import jax.numpy as jnp

from repro import runtime
from repro.gnn.models import ZooSpec
from repro.graphs.datasets import make_dataset

# paper Table-III names -> zoo architectures
NETWORKS = {"gcn": "gcn", "graphsage": "sage_mean",
            "graphsage_pool": "sage_max"}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="cora",
                    choices=["cora", "citeseer", "pubmed"])
    ap.add_argument("--network", default="gcn", choices=sorted(NETWORKS))
    ap.add_argument("--epochs", type=int, default=30,
                    help="full-batch steps (or mini-batch steps with "
                         "--batch-nodes)")
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--shard-n", type=int, default=512,
                    help="planner cap on nodes per shard (the paper's n)")
    ap.add_argument("--batch-nodes", type=int, default=0,
                    help="0 = full-batch; >0 neighbor-samples this many "
                         "seed nodes per step")
    ap.add_argument("--backend", default=None,
                    choices=["pallas", "jax", "reference"],
                    help="kernel backend (default: REPRO_KERNEL_BACKEND "
                         "env, else pallas — interpret mode on CPU)")
    args = ap.parse_args()

    ds = make_dataset(args.dataset)
    print(f"{ds.profile.name}: {ds.profile.num_nodes} nodes, "
          f"{ds.edges.shape[0]} edges, {ds.profile.feature_dim} features "
          f"({ds.size_mb:.1f} MB)")

    spec = ZooSpec(NETWORKS[args.network], ds.profile.feature_dim,
                   args.hidden, ds.profile.num_classes, num_layers=2)
    t0 = time.time()
    result = runtime.fit(spec, ds, steps=args.epochs, lr=5e-3,
                         backend=args.backend, max_shard_n=args.shard_n,
                         batch_nodes=args.batch_nodes, fanout=(10, 5),
                         log_every=max(1, args.epochs // 10))
    exe = result.executable               # trained weights already swapped in
    print(exe.summary())

    labels = jnp.asarray(ds.labels)
    logits = exe.forward()
    test_acc = float(jnp.mean(
        (jnp.argmax(logits, -1) == labels)[~ds.train_mask]))
    print(f"trained in {time.time() - t0:.1f}s: "
          f"train-acc {result.train_accuracy():.3f} test-acc {test_acc:.3f}")
    classes, probs = exe.predict([0, 1, 2])
    print(f"predict([0,1,2]) -> classes {classes.tolist()} "
          f"(p={[round(float(p), 3) for p in probs]})")
    print("done.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
